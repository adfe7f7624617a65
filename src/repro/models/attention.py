"""Attention: blockwise (flash-style) training/prefill attention, KV-cache decode,
and cross-attention.

The training/prefill path is a pure-JAX online-softmax scan over KV blocks — the
TPU-idiomatic formulation (bounded VMEM working set, MXU-aligned blocks). It is
also the numerical oracle for the Pallas flash kernel in ``repro.kernels``.

Head layout: q is (B, S, H, hd); k/v are stored with K kv-heads and repeated to H
on the fly (a local broadcast when kv-heads are replicated or evenly sharded —
no resharding collective is induced; see DESIGN.md §4).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import (apply_rope, head_rms_norm, rms_norm,
                                 yarn_frequencies, yarn_mscale)

_NEG_INF = -1e30


def repeat_kv(x: jax.Array, num_heads: int) -> jax.Array:
    """(B, S, K, hd) -> (B, S, H, hd) by repeating each kv head H//K times."""
    b, s, k, hd = x.shape
    if k == num_heads:
        return x
    reps = num_heads // k
    return jnp.repeat(x, reps, axis=2)


def dense_attention(q, k, v, *, causal: bool, q_offset: int = 0,
                    scale: Optional[float] = None) -> jax.Array:
    """Reference masked attention (materializes scores). Identical math to
    blockwise_attention; used in analysis mode and as the kernel oracle."""
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    if scale is None:
        scale = 1.0 / np.sqrt(hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    if causal:
        q_pos = q_offset + jnp.arange(sq)
        mask = q_pos[:, None] >= jnp.arange(skv)[None, :]
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def blockwise_attention(
    q: jax.Array,            # (B, Sq, H, hd)
    k: jax.Array,            # (B, Skv, H, hd)  (already repeated)
    v: jax.Array,            # (B, Skv, H, hd_v)
    *,
    causal: bool,
    q_offset: int = 0,       # absolute position of q[0] (prefill continuation)
    kv_block: int = 1024,
    scale: Optional[float] = None,   # default 1 / sqrt(hd)
) -> jax.Array:
    """Online-softmax attention, scanning KV in blocks. fp32 accumulators.
    v's head size may differ from q's and k's (latent attention).

    Analysis mode uses the dense masked form (identical FLOPs; its backward
    all-reduces make the reported collective term an UPPER BOUND on the
    production blockwise form — both measured, EXPERIMENTS.md §Perf)."""
    from repro.models.modes import in_analysis_mode
    if in_analysis_mode():
        return dense_attention(q, k, v, causal=causal, q_offset=q_offset,
                               scale=scale)
    b, sq, h, hd = q.shape
    skv, hv = k.shape[1], v.shape[-1]
    kv_block = min(kv_block, skv)
    n_blocks = (skv + kv_block - 1) // kv_block
    pad = n_blocks * kv_block - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    if scale is None:
        scale = 1.0 / np.sqrt(hd)
    qf = (q.astype(jnp.float32) * scale).transpose(0, 2, 1, 3)  # (B,H,Sq,hd)
    kb = k.transpose(0, 2, 1, 3).reshape(b, h, n_blocks, kv_block, hd)
    vb = v.transpose(0, 2, 1, 3).reshape(b, h, n_blocks, kv_block, hv)
    q_pos = q_offset + jnp.arange(sq)

    def body(carry, inputs):
        acc, row_max, row_sum = carry
        blk_idx, k_blk, v_blk = inputs
        kv_pos = blk_idx * kv_block + jnp.arange(kv_block)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_blk.astype(jnp.float32))
        mask = kv_pos[None, :] < skv  # padding
        if causal:
            mask = mask & (q_pos[:, None] >= kv_pos[None, :])
        s = jnp.where(mask[None, None], s, _NEG_INF)
        blk_max = jnp.max(s, axis=-1)
        new_max = jnp.maximum(row_max, blk_max)
        corr = jnp.exp(row_max - new_max)
        p = jnp.exp(s - new_max[..., None])
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32))
        row_sum = row_sum * corr + jnp.sum(p, axis=-1)
        return (acc, new_max, row_sum), None

    init = (
        jnp.zeros((b, h, sq, hv), jnp.float32),
        jnp.full((b, h, sq), _NEG_INF, jnp.float32),
        jnp.zeros((b, h, sq), jnp.float32),
    )
    # remat the block body: without this the scan saves the fp32 (B,H,Sq,BK)
    # score/prob tensors of EVERY block for backward (measured ~17 GB/device
    # at deepseek-67b train_4k; with remat only the (B,H,Sq,hd) carries stack)
    (acc, _, row_sum), _ = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False), init,
        (jnp.arange(n_blocks), kb.transpose(2, 0, 1, 3, 4),
         vb.transpose(2, 0, 1, 3, 4)),
    )
    out = acc / jnp.maximum(row_sum[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # (B,Sq,H,hd)


def decode_attention(
    q: jax.Array,          # (B, 1, H, hd)
    k_cache: jax.Array,    # (B, T, K, hd)
    v_cache: jax.Array,    # (B, T, K, hd)
    cur_len: jax.Array,    # () int32 — number of valid cache positions
    num_heads: int,
) -> jax.Array:
    from repro.parallel.constraints import BATCH, constrain
    b, t, kh, hd = k_cache.shape
    k = repeat_kv(k_cache, num_heads)
    v = repeat_kv(v_cache, num_heads)
    scale = 1.0 / np.sqrt(hd)
    # flash-decode sharding: keep scores SEQUENCE-sharded over "model" (the
    # cache's layout) — XLA then all-gathers the tiny q heads instead of
    # replicating the multi-GB cache (observed "involuntary full
    # rematerialization" warnings + 75 ms/step collective otherwise); the
    # softmax reduction becomes a cheap cross-shard psum.
    q = constrain((q.astype(jnp.float32) * scale).astype(k.dtype),
                  BATCH, None, None, None)
    # MXU-native mixed precision: bf16 inputs, fp32 accumulation — never
    # materializes an fp32 copy of the (B, T, H, hd) repeated cache
    s = jnp.einsum("bqhd,bthd->bhqt", q, k,
                   preferred_element_type=jnp.float32)
    # match the cache's sequence sharding: batch=1 caches (long_500k) shard T
    # over ("data","model"); batched decode shards T over "model" only
    t_parts = ("data", "model") if b == 1 else "model"
    s = constrain(s, BATCH, None, None, t_parts)
    mask = jnp.arange(t)[None, None, None, :] < cur_len
    s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqt,bthd->bqhd", p.astype(k.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(k_cache.dtype)


# --------------------------------------------------------------------------- #
# Full attention sub-block (projections + rope + attention + out-proj)
# --------------------------------------------------------------------------- #
def attn_init(key, cfg, dtype) -> Dict:
    if cfg.is_mla:
        return mla_init(key, cfg, dtype)
    hd = cfg.resolved_head_dim
    h, kh, d = cfg.num_heads, cfg.num_kv_heads, cfg.d_model
    ks = jax.random.split(key, 4)
    sc = 1.0 / np.sqrt(d)
    so = 1.0 / np.sqrt(h * hd)
    p = {
        "wq": (jax.random.normal(ks[0], (d, h * hd)) * sc).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d, kh * hd)) * sc).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d, kh * hd)) * sc).astype(dtype),
        "wo": (jax.random.normal(ks[3], (h * hd, d)) * so).astype(dtype),
    }
    if cfg.use_qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def _project_qkv(p: Dict, cfg, x: jax.Array, positions: jax.Array,
                 *, rope: bool = True) -> Tuple[jax.Array, jax.Array, jax.Array]:
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(b, s, cfg.num_heads, hd)
    k = (x @ p["wk"]).reshape(b, s, cfg.num_kv_heads, hd)
    v = (x @ p["wv"]).reshape(b, s, cfg.num_kv_heads, hd)
    if cfg.use_qk_norm:
        q = head_rms_norm(q, p["q_norm"])
        k = head_rms_norm(k, p["k_norm"])
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _ulysses(q, k, v):
    """Sequence->head resharding (DeepSpeed-Ulysses style) — REFUTED under
    XLA's pre-Shardy auto-partitioner: the head-sharding constraints added
    ~13.9 GB/layer of all-to-alls WITHOUT removing the partial-sum
    all-reduces (the KV repeat broadcast defeats the partitioner; measured,
    EXPERIMENTS.md §Perf). Kept as an identity hook for when Shardy lands."""
    return q, k, v


def self_attention(p: Dict, cfg, x: jax.Array, *, causal: bool = True,
                   rope: bool = True, kv_block: int = 1024) -> jax.Array:
    if cfg.is_mla:
        return mla_attention(p, cfg, x, kv_block=kv_block)
    b, s, _ = x.shape
    positions = jnp.arange(s)
    q, k, v = _project_qkv(p, cfg, x, positions, rope=rope)
    k = repeat_kv(k, cfg.num_heads)
    v = repeat_kv(v, cfg.num_heads)
    q, k, v = _ulysses(q, k, v)
    out = blockwise_attention(q, k, v, causal=causal, kv_block=kv_block)
    return out.reshape(b, s, -1) @ p["wo"]


def self_attention_prefill(p: Dict, cfg, x: jax.Array, cache_len: int,
                           kv_block: int = 1024):
    """Returns (out, (k_cache, v_cache)) with caches padded to ``cache_len``."""
    b, s, _ = x.shape
    positions = jnp.arange(s)
    q, k, v = _project_qkv(p, cfg, x, positions)
    qh, kh, vh = _ulysses(q, repeat_kv(k, cfg.num_heads),
                          repeat_kv(v, cfg.num_heads))
    out = blockwise_attention(qh, kh, vh, causal=True, kv_block=kv_block)
    out = out.reshape(b, s, -1) @ p["wo"]
    pad = cache_len - s
    k_c = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    v_c = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return out, (k_c, v_c)


def self_attention_decode(p: Dict, cfg, x: jax.Array, cache: Tuple,
                          index: jax.Array):
    """One-token decode. x: (B, 1, D); cache: (k,v) each (B, T, K, hd);
    index: () current position. Returns (out, new_cache)."""
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    positions = jnp.full((1,), index, jnp.int32)
    q, k, v = _project_qkv(p, cfg, x, positions)
    k_cache, v_cache = cache
    k_cache = jax.lax.dynamic_update_slice(k_cache, k, (0, index, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v, (0, index, 0, 0))
    out = decode_attention(q, k_cache, v_cache, index + 1, cfg.num_heads)
    out = out.reshape(b, 1, -1) @ p["wo"]
    return out, (k_cache, v_cache)


# --------------------------------------------------------------------------- #
# Multi-head latent attention (DeepSeek-V2), for training: the latent is
# expanded to every head's K and V (not absorbed into the projections)
# --------------------------------------------------------------------------- #
def mla_init(key, cfg, dtype) -> Dict:
    h, d, r = cfg.num_heads, cfg.d_model, cfg.kv_lora_rank
    nope, rope, hv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape) / np.sqrt(fan_in)).astype(dtype)
    return {
        "wq": w(ks[0], (d, h * (nope + rope)), d),    # per head [nope, rope]
        "wkv_a": w(ks[1], (d, r + rope), d),          # [latent, shared rope key]
        "kv_norm": jnp.zeros((r,), dtype),
        "wkv_b": w(ks[2], (r, h * (nope + hv)), r),   # per head [k nope, v]
        "wo": w(ks[3], (h * hv, d), h * hv),
    }


def mla_softmax_scale(cfg) -> float:
    """1 / sqrt(q.k head size), times YaRN's mscale squared."""
    m = yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
    return m * m / np.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def mla_attention(p: Dict, cfg, x: jax.Array, *,
                  kv_block: int = 1024) -> jax.Array:
    """Causal self-attention of (B, S, D): queries of `nope + rope` per head,
    keys of the RMS-normed latent's `nope` part per head beside one rotary
    key shared by every head, values of `v_head_dim`."""
    with jax.named_scope("mla"):
        b, s, _ = x.shape
        h, r = cfg.num_heads, cfg.kv_lora_rank
        nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        positions = jnp.arange(s)
        # beta_fast 32 and beta_slow 1: DeepSeek-V2's published rope_scaling
        freqs = yarn_frequencies(
            rope, cfg.rope_theta, cfg.yarn_factor,
            cfg.yarn_original_max_position, 32.0,
            1.0) if cfg.yarn_factor else None
        q = (x @ p["wq"]).reshape(b, s, h, nope + rope)
        kv_a = x @ p["wkv_a"]
        kv = (rms_norm(kv_a[..., :r], p["kv_norm"]) @ p["wkv_b"]).reshape(
            b, s, h, nope + cfg.v_head_dim)
        q_pe = apply_rope(q[..., nope:], positions, cfg.rope_theta, freqs)
        k_pe = apply_rope(kv_a[:, :, None, r:], positions, cfg.rope_theta,
                          freqs)
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (b, s, h, rope))], axis=-1)
        out = blockwise_attention(q, k, kv[..., nope:], causal=True,
                                  kv_block=kv_block,
                                  scale=mla_softmax_scale(cfg))
        return out.reshape(b, s, -1) @ p["wo"]


# --------------------------------------------------------------------------- #
# Cross-attention (Whisper decoder)
# --------------------------------------------------------------------------- #
def cross_attn_init(key, cfg, dtype) -> Dict:
    return attn_init(key, cfg, dtype)


def cross_attention(p: Dict, cfg, x: jax.Array, enc_kv: Tuple[jax.Array, jax.Array],
                    ) -> jax.Array:
    """x: (B, S, D); enc_kv: precomputed (k, v) each (B, Senc, K, hd)."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(b, s, cfg.num_heads, hd)
    k, v = enc_kv
    qh, kh, vh = _ulysses(q, repeat_kv(k, cfg.num_heads),
                          repeat_kv(v, cfg.num_heads))
    out = blockwise_attention(qh, kh, vh, causal=False)
    return out.reshape(b, s, -1) @ p["wo"]


def cross_kv(p: Dict, cfg, enc_out: jax.Array) -> Tuple[jax.Array, jax.Array]:
    b, s, _ = enc_out.shape
    hd = cfg.resolved_head_dim
    k = (enc_out @ p["wk"]).reshape(b, s, cfg.num_kv_heads, hd)
    v = (enc_out @ p["wv"]).reshape(b, s, cfg.num_kv_heads, hd)
    return k, v
