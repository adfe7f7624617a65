"""Pallas TPU GQA decode-attention kernel (one query token vs. a long KV
cache).

Grid = (B, T/BT); the second axis streams the cache in (BT, K, hd) tiles
through VMEM with online-softmax accumulators held in scratch across it —
decode is HBM-bandwidth-bound, so the tile stream is exactly the cache read
stream. The current lengths arrive as a scalar-prefetch operand (SMEM):
tiles wholly past a row's length are skipped and the tail tile is masked.

GQA mapping: kv head k is read from the tile as a strided (BT, hd) slice.
Every q head is scored against each kv head and a row mask keeps the
G = H/K q heads that read that kv head — G x redundant MXU work, free on a
bandwidth-bound kernel, and it slices no q row out of its (8, 128) tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, bt: int, h: int, kh: int, hd: int, scale: float):
    b, j = pl.program_id(0), pl.program_id(1)
    cur_len = len_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j * bt < cur_len)
    def _tile():
        q = q_ref[0].astype(jnp.float32) * scale            # (H, hd)
        q_head = jax.lax.broadcasted_iota(jnp.int32, (h, bt), 0) // (h // kh)
        s = jnp.full((h, bt), _NEG_INF, jnp.float32)
        for k in range(kh):
            k_k = k_ref[0, :, k, :].astype(jnp.float32)     # (BT, hd)
            s_k = jax.lax.dot_general(q, k_k, _NT,
                                      preferred_element_type=jnp.float32)
            s = jnp.where(q_head == k, s_k, s)
        pos = j * bt + jax.lax.broadcasted_iota(jnp.int32, (h, bt), 1)
        s = jnp.where(pos < cur_len, s, _NEG_INF)
        m_prev = m_ref[...]                                 # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        o_head = jax.lax.broadcasted_iota(jnp.int32, (h, hd), 0) // (h // kh)
        pv = jnp.zeros((h, hd), jnp.float32)
        for k in range(kh):
            v_k = v_ref[0, :, k, :].astype(jnp.float32)
            pv_k = jax.lax.dot_general(p, v_k, _NN,
                                       preferred_element_type=jnp.float32)
            pv = jnp.where(o_head == k, pv_k, pv)
        acc_ref[...] = acc_ref[...] * corr + pv

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cur_len: jax.Array, *, bt: int = 512,
                     interpret: bool) -> jax.Array:
    """q: (B, 1, H, hd); caches: (B, T, K, hd); cur_len: () int32.
    Returns (B, 1, H, hd)."""
    b, _, h, hd = q.shape
    t, kh = k_cache.shape[1], k_cache.shape[2]
    bt = min(bt, t)
    assert t % bt == 0 and h % kh == 0
    scale = 1.0 / np.sqrt(hd)

    kernel = functools.partial(_decode_kernel, bt=bt, h=h, kh=kh, hd=hd,
                               scale=scale)
    lens = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (b,))

    def tile_index(i, j, lens):
        # past a row's length, stay on its last live tile: an unchanged
        # block index issues no new DMA
        return (i, jnp.minimum(j, jnp.maximum(lens[i] - 1, 0) // bt), 0, 0)

    tile = pl.BlockSpec((1, bt, kh, hd), tile_index)
    row = pl.BlockSpec((1, h, hd), lambda i, j, lens: (i, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, t // bt),
            in_specs=[row, tile, tile],
            out_specs=row,
            scratch_shapes=[pltpu.VMEM((h, hd), jnp.float32),
                            pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, 1), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        interpret=interpret,
    )(lens, q[:, 0], k_cache, v_cache)
    return out[:, None]
