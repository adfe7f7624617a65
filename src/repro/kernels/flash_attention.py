"""Pallas TPU flash-attention forward kernel.

Tiling: grid = (B*H, Sq/BQ); each grid cell holds one (BQ, hd) query tile in
VMEM and streams KV in (BK, hd) tiles with online-softmax accumulators in
fp32 VREGs. BQ/BK default 128/256 — MXU-aligned (multiples of 128 on the
contracting/lane dims); the VMEM working set is
BQ*hd + 2*BK*hd + BQ*BK floats, far under the ~16 MB/core budget.

Validated against the pure-jnp oracle (repro.kernels.ref / dense_attention)
in interpret mode across shape/dtype sweeps; used for training via
jax.custom_vjp with a rematerializing blockwise backward.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

_NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, causal: bool, sq: int,
                      skv: int, bq: int, bk: int, scale: float):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale            # (BQ, hd)
    hd = q.shape[-1]
    n_kv = skv // bk

    def body(j, carry):
        acc, m_i, l_i = carry
        k = k_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)   # (BK, hd)
        v = v_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (BQ, BK)
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_i - m_new)
        acc = acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        l_i = l_i * corr + jnp.sum(p, axis=1)
        return acc, m_new, l_i

    if causal:
        # skip blocks strictly above the diagonal
        last = jnp.minimum(((qi + 1) * bq + bk - 1) // bk, n_kv)
    else:
        last = n_kv
    acc, m_i, l_i = jax.lax.fori_loop(
        0, last, body,
        (jnp.zeros((bq, hd), jnp.float32),
         jnp.full((bq,), _NEG_INF, jnp.float32),
         jnp.zeros((bq,), jnp.float32)))
    o_ref[0] = (acc / jnp.maximum(l_i, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, bq: int = 128, bk: int = 256,
                        interpret: bool) -> jax.Array:
    """q: (B, Sq, H, hd); k/v: (B, Skv, H, hd) (kv already head-repeated).
    Returns (B, Sq, H, hd)."""
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    bq = min(bq, sq)
    bk = min(bk, skv)
    assert sq % bq == 0 and skv % bk == 0, "seq dims must tile evenly"
    scale = 1.0 / np.sqrt(hd)

    qr = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kr = k.transpose(0, 2, 1, 3).reshape(b * h, skv, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(b * h, skv, hd)

    kernel = functools.partial(_flash_fwd_kernel, causal=causal, sq=sq,
                               skv=skv, bq=bq, bk=bk, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, skv, hd), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, skv, hd), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, hd), q.dtype),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool, bq: int, bk: int,
                    interpret: bool):
    return flash_attention_fwd(q, k, v, causal=causal, bq=bq, bk=bk,
                               interpret=interpret)


def _fwd(q, k, v, causal, bq, bk, interpret):
    o = flash_attention_fwd(q, k, v, causal=causal, bq=bq, bk=bk,
                            interpret=interpret)
    return o, (q, k, v)


def _bwd(causal, bq, bk, interpret, res, do):
    """Rematerializing backward: re-derive gradients with the blockwise
    reference (pure-jnp oracle) — numerically the same attention."""
    q, k, v = res
    from repro.models.attention import blockwise_attention

    def f(q, k, v):
        return blockwise_attention(q, k, v, causal=causal, kv_block=bk)

    _, vjp = jax.vjp(f, q, k, v)
    return vjp(do)


flash_attention.defvjp(_fwd, _bwd)
