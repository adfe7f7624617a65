"""Pallas kernel validation: shape/dtype sweeps vs. pure-jnp oracles
(interpret mode on CPU). Deliverable (c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.ref import (decode_attention_ref, flash_attention_ref,
                               ssd_recurrent_ref, ssd_ref)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,s,h,hd", [(1, 128, 2, 64), (2, 256, 4, 64),
                                      (2, 128, 4, 128), (1, 512, 8, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(b, s, h, hd, dtype, causal, rng):
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, h, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, h, hd)), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, bq=64, bk=64)
    ref = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_attention_grad(rng):
    b, s, h, hd = 1, 128, 2, 64
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    g1 = jax.grad(lambda q: ops.flash_attention(
        q, k, v, causal=True, bq=64, bk=64).sum())(q)
    g2 = jax.grad(lambda q: flash_attention_ref(q, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b,t,h,kh,hd", [(2, 128, 4, 2, 64), (1, 256, 8, 1, 64),
                                         (2, 64, 4, 4, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("cur_len", [1, 63, 128])
def test_decode_attention_sweep(b, t, h, kh, hd, dtype, cur_len, rng):
    cur_len = min(cur_len, t)
    q = jnp.asarray(rng.normal(size=(b, 1, h, hd)), dtype)
    kc = jnp.asarray(rng.normal(size=(b, t, kh, hd)), dtype)
    vc = jnp.asarray(rng.normal(size=(b, t, kh, hd)), dtype)
    out = ops.decode_attention(q, kc, vc, jnp.asarray(cur_len), bt=32)
    ref = decode_attention_ref(q, kc, vc, jnp.asarray(cur_len), h)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 64, 8, 16, 32, 16), (1, 128, 8, 32, 64, 32), (2, 48, 16, 16, 16, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_kernel_sweep(b, s, h, p, n, chunk, dtype, rng):
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), dtype)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, size=(b, s, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 2.0, size=(h,)), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(b, s, n)), dtype)
    cm = jnp.asarray(rng.normal(size=(b, s, n)), dtype)
    yk, sk = ops.ssd(x, dt, a, bm, cm, chunk=chunk)
    yo, so = ssd_ref(x, dt, a, bm, cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(yk, np.float32),
                               np.asarray(yo, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(sk), np.asarray(so),
                               rtol=1e-3, atol=1e-3)


def test_ssd_chunked_matches_recurrence(rng):
    """The chunked algorithm (and hence the kernel) must match the O(S)
    token-by-token recurrence — the ground-truth SSM semantics."""
    b, s, h, p, n = 2, 96, 4, 16, 32
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, size=(b, s, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 2.0, size=(h,)), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)
    cm = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)
    yo, so = ssd_ref(x, dt, a, bm, cm, chunk=32)
    yr, sr = ssd_recurrent_ref(x, dt, a, bm, cm)
    np.testing.assert_allclose(np.asarray(yo), np.asarray(yr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(so), np.asarray(sr),
                               rtol=1e-4, atol=1e-4)


def test_ssd_initial_state_threading(rng):
    """Splitting a sequence in two with state carry == one full pass."""
    b, s, h, p, n = 1, 64, 4, 16, 16
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, size=(b, s, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 2.0, size=(h,)), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)
    cm = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)
    y_full, s_full = ssd_ref(x, dt, a, bm, cm, chunk=16)
    half = s // 2
    y1, s1 = ssd_ref(x[:, :half], dt[:, :half], a, bm[:, :half],
                     cm[:, :half], chunk=16)
    y2, s2 = ssd_ref(x[:, half:], dt[:, half:], a, bm[:, half:],
                     cm[:, half:], chunk=16, initial_state=s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               rtol=1e-4, atol=1e-4)
