"""Pallas TPU kernel for the Mamba2 SSD intra-chunk block.

Per grid cell (batch-chunk i, head h): computes the quadratic intra-chunk
output and the chunk's end-state contribution — the (Lc, Lc) score tile
lives only in VMEM (the pure-JAX form materializes it in HBM per chunk).
The cumulative decay, the chunk decay and the cheap inter-chunk recurrence
(combine over chunk states) stay in JAX (associative scan) — same split as
the Mamba2 paper's SSD algorithm.

Layout: every operand is head-major with (Lc, P), (Lc, N), (1, Lc) or
(Lc, 1) as its last two dims, so each block's minor dims are either whole
array dims or multiples of the TPU's (8, 128) tile. Per-head vectors come in
as a (1, Lc) row and, where the kernel needs them down a column, as an
(Lc, 1) column. VMEM per cell at Lc=256, N=128, P=64: the (Lc, Lc) tiles plus
(Lc, N) B/C and (Lc, P) x/y ~= 1.3 MB.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _ssd_chunk_kernel(x_ref, dt_ref, csr_ref, csc_ref, w_ref, b_ref, bt_ref,
                      c_ref, y_ref, st_ref, *, lc: int):
    x = x_ref[0, 0].astype(jnp.float32)        # (Lc, P)
    dt = dt_ref[0, 0]                          # (1, Lc)
    cs_row = csr_ref[0, 0]                     # (1, Lc)  cumsum(dt * a)
    cs_col = csc_ref[0, 0]                     # (Lc, 1)
    w = w_ref[0, 0]                            # (1, Lc)  dt * exp(last - cs)
    bm = b_ref[0].astype(jnp.float32)          # (Lc, N)
    cm = c_ref[0].astype(jnp.float32)          # (Lc, N)
    bt = bt_ref[0].astype(jnp.float32)         # (N, Lc)

    cb = jax.lax.dot_general(cm, bm, _NT, preferred_element_type=jnp.float32)
    idx = jax.lax.broadcasted_iota(jnp.int32, (lc, lc), 0)
    jdx = jax.lax.broadcasted_iota(jnp.int32, (lc, lc), 1)
    att = jnp.where(idx >= jdx, cb * jnp.exp(cs_col - cs_row) * dt, 0.0)
    y = jax.lax.dot_general(att, x, _NN, preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)
    st_ref[0, 0] = jax.lax.dot_general(bt * w, x, _NN,
                                       preferred_element_type=jnp.float32)


def ssd_intra_chunk(x: jax.Array, dt: jax.Array, cs: jax.Array,
                    b_mat: jax.Array, c_mat: jax.Array, *, chunk: int,
                    interpret: bool) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, H, P); dt: (B, S, H) (post-softplus); cs: (B, S, H) the
    within-chunk cumulative sum of dt * a; b/c: (B, S, N). S must divide by
    chunk. Returns (y_intra (B,S,H,P), chunk_states (B,NC,H,N,P))."""
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    lc = min(chunk, s)
    assert s % lc == 0
    nc = s // lc
    bn = bsz * nc

    def heads_major(v):                        # (B, S, H) -> (BN, H, Lc)
        return v.reshape(bn, lc, h).transpose(0, 2, 1)

    dt_h = heads_major(dt.astype(jnp.float32))
    cs_h = heads_major(cs)
    w_h = dt_h * jnp.exp(cs_h[..., -1:] - cs_h)
    xr = x.reshape(bn, lc, h, p).transpose(0, 2, 1, 3)
    br = b_mat.reshape(bn, lc, n)
    cr = c_mat.reshape(bn, lc, n)

    row = pl.BlockSpec((1, 1, 1, lc), lambda i, j: (i, j, 0, 0))
    mat = lambda d: pl.BlockSpec((1, lc, d), lambda i, j: (i, 0, 0))
    y, states = pl.pallas_call(
        lambda *refs: _ssd_chunk_kernel(*refs, lc=lc),
        grid=(bn, h),
        in_specs=[
            pl.BlockSpec((1, 1, lc, p), lambda i, j: (i, j, 0, 0)),
            row, row,
            pl.BlockSpec((1, 1, lc, 1), lambda i, j: (i, j, 0, 0)),
            row, mat(n),
            pl.BlockSpec((1, n, lc), lambda i, j: (i, 0, 0)),
            mat(n),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, lc, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, n, p), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bn, h, lc, p), x.dtype),
            jax.ShapeDtypeStruct((bn, h, n, p), jnp.float32),
        ],
        interpret=interpret,
    )(xr, dt_h[:, :, None], cs_h[:, :, None], cs_h[..., None],
      w_h[:, :, None], br, br.transpose(0, 2, 1), cr)

    return (y.transpose(0, 2, 1, 3).reshape(bsz, s, h, p),
            states.reshape(bsz, nc, h, n, p))


def ssd(x: jax.Array, dt: jax.Array, a: jax.Array, b_mat: jax.Array,
        c_mat: jax.Array, *, chunk: int = 256, initial_state=None,
        interpret: bool):
    """Full SSD = Pallas intra-chunk kernel + JAX inter-chunk combine.
    Matches repro.models.mamba2.ssd_chunked (the oracle)."""
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    lc = min(chunk, s)
    pad = (-s) % lc
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0)))
    sp = s + pad
    nc = sp // lc

    da = (dt.astype(jnp.float32) * a.astype(jnp.float32)[None, None, :]
          ).reshape(bsz, nc, lc, h)
    cs = jnp.cumsum(da, axis=2)                       # (B, NC, Lc, H)
    chunk_decay = jnp.exp(cs[:, :, -1])               # (B, NC, H)

    y_intra, chunk_states = ssd_intra_chunk(
        x, dt, cs.reshape(bsz, sp, h), b_mat, c_mat, chunk=lc,
        interpret=interpret)

    if initial_state is None:
        initial_state = jnp.zeros((bsz, h, n, p), jnp.float32)

    # inter-chunk: inclusive associative scan over (decay, state)
    def combine(u, w):
        d1, s1 = u
        d2, s2 = w
        return d1 * d2, s1 * d2[..., None, None] + s2

    dec_sw = jnp.moveaxis(chunk_decay, 1, 0)
    st_sw = jnp.moveaxis(chunk_states, 1, 0)
    run_dec, run_st = jax.lax.associative_scan(combine, (dec_sw, st_sw))
    init = initial_state
    prev = jnp.concatenate(
        [init[None], run_st[:-1] + run_dec[:-1][..., None, None] * init[None]],
        axis=0)                                       # (NC, B, H, N, P)
    prev = jnp.moveaxis(prev, 0, 1)

    # y_inter = C_i . S_prev * exp(cs_i)
    cm = c_mat.astype(jnp.float32).reshape(bsz, nc, lc, n)
    y_inter = jnp.einsum("bcin,bchnp->bcihp", cm, prev) * \
        jnp.exp(cs)[..., None]
    y = y_intra.astype(jnp.float32) + y_inter.reshape(bsz, sp, h, p)
    y = y[:, :s]
    final_state = run_st[-1] + run_dec[-1][..., None, None] * init
    return y.astype(x.dtype), final_state
