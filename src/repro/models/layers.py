"""Shared neural-net layers: norms, RoPE, MLP flavors, embeddings.

All functions are pure; parameters are plain dict pytrees. Compute runs in the
array's dtype (bf16 in production) with fp32 accumulation where it matters
(norm statistics, softmax, logits).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def head_rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Qwen3-style qk-norm over the head dim of (..., heads, head_dim)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def rope_frequencies(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def yarn_frequencies(head_dim: int, theta: float, factor: float,
                     original: int, beta_fast: float,
                     beta_slow: float) -> np.ndarray:
    """YaRN's rotary frequencies (DeepSeek-V2's `DeepseekV2YarnRotaryEmbedding`):
    the plain frequencies above `beta_fast` rotations over the original
    context, those divided by `factor` below `beta_slow`, a linear ramp
    between."""
    def dim_of(rotations: float) -> float:
        return (head_dim * np.log(original / (rotations * 2 * np.pi))
                / (2 * np.log(theta)))
    low = max(int(np.floor(dim_of(beta_fast))), 0)
    high = min(int(np.ceil(dim_of(beta_slow))), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    extra = rope_frequencies(head_dim, theta)
    return (extra / factor * ramp + extra * (1 - ramp)).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * float(np.log(factor)) + 1.0 if factor > 1 else 1.0


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               freqs: Optional[np.ndarray] = None) -> jax.Array:
    """Rotary embedding. x: (..., seq, heads, head_dim); positions: (..., seq).
    `freqs` (head_dim / 2,) replaces the plain frequencies of `theta`."""
    dtype = x.dtype
    head_dim = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(head_dim, theta) if freqs is None
                        else freqs)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)


def sinusoidal_positions(seq: int, d_model: int) -> np.ndarray:
    """Whisper-style fixed sinusoidal embeddings for encoder frames."""
    pos = np.arange(seq, dtype=np.float32)[:, None]
    dim = np.arange(d_model // 2, dtype=np.float32)[None, :]
    inv = np.exp(-np.log(10_000.0) * dim / max(d_model // 2 - 1, 1))
    ang = pos * inv
    return np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)


# --------------------------------------------------------------------------- #
# MLP flavors
# --------------------------------------------------------------------------- #
def mlp_apply(params: Dict[str, jax.Array], x: jax.Array, mlp_type: str) -> jax.Array:
    if mlp_type == "swiglu":
        g = x @ params["w_gate"]
        u = x @ params["w_up"]
        return (jax.nn.silu(g) * u) @ params["w_down"]
    if mlp_type == "geglu":
        g = x @ params["w_gate"]
        u = x @ params["w_up"]
        return (jax.nn.gelu(g, approximate=True) * u) @ params["w_down"]
    if mlp_type == "sq_relu":
        u = jax.nn.relu(x @ params["w_up"])
        return jnp.square(u) @ params["w_down"]
    if mlp_type == "gelu":
        return jax.nn.gelu(x @ params["w_up"], approximate=True) @ params["w_down"]
    raise ValueError(f"unknown mlp_type {mlp_type!r}")


def mlp_init(key: jax.Array, d_model: int, d_ff: int, mlp_type: str, dtype) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    scale_in = 1.0 / np.sqrt(d_model)
    scale_out = 1.0 / np.sqrt(d_ff)
    p = {
        "w_up": (jax.random.normal(k2, (d_model, d_ff)) * scale_in).astype(dtype),
        "w_down": (jax.random.normal(k3, (d_ff, d_model)) * scale_out).astype(dtype),
    }
    if mlp_type in ("swiglu", "geglu"):
        p["w_gate"] = (jax.random.normal(k1, (d_model, d_ff)) * scale_in).astype(dtype)
    return p


def embed_init(key: jax.Array, vocab: int, d_model: int, dtype) -> Dict:
    return {"w": (jax.random.normal(key, (vocab, d_model)) * 0.02).astype(dtype)}


def embed_lookup(params: Dict, tokens: jax.Array) -> jax.Array:
    return params["w"][tokens]


def chunked_xent(params: Dict, x: jax.Array, labels: jax.Array,
                 *, chunk: int = 512) -> jax.Array:
    """Mean next-token cross-entropy WITHOUT materializing (B, S, V) logits:
    scan over sequence chunks with a rematerialized body, so the live logits
    buffer is (B, chunk, V/tp) — the standard big-vocab memory trick.

    x: (B, S, D) final hidden states; labels: (B, S)."""
    from repro.models.modes import in_analysis_mode
    from repro.parallel.constraints import BATCH, constrain
    if in_analysis_mode():  # cost-exact: no scan (bodies are counted once)
        logits = jnp.einsum("bsd,vd->bsv", bf16_grad_barrier(x),
                            constrain(params["w"], "model", None),
                            preferred_element_type=jnp.float32)
        logits = constrain(logits, BATCH, None, "model")
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        lab = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - lab)
    b, s, d = x.shape
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
    nc = (s + pad) // chunk
    x = bf16_grad_barrier(x)
    xc = x.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, nc, chunk).transpose(1, 0, 2)
    mask = (jnp.arange(nc * chunk) < s).reshape(nc, chunk)
    # constraint propagates to the cotangent: d(w) accumulates vocab-sharded
    # instead of as a full fp32 (V, D) replica on every device (measured
    # 3x3.4 GB at deepseek scale)
    w = constrain(params["w"], "model", None)

    def body(acc, inp):
        x_k, l_k, m_k = inp
        logits = jnp.einsum("bsd,vd->bsv", x_k, w,
                            preferred_element_type=jnp.float32)
        logits = constrain(logits, BATCH, None, "model")
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        lab = jnp.take_along_axis(logits, l_k[..., None], axis=-1)[..., 0]
        return acc + jnp.sum((lse - lab) * m_k[None, :]), None

    body = jax.checkpoint(body, prevent_cse=False)
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            (xc, lc, mask))
    return total / (b * s)


@jax.custom_vjp
def bf16_grad_barrier(x: jax.Array) -> jax.Array:
    """Identity whose cotangent is cast to bf16: keeps the backward residual
    stream in bf16 (the fp32 logits otherwise push fp32 cotangents through
    every layer — 2x the activation-grad HBM traffic and footprint)."""
    return x


def _bgb_fwd(x):
    return x, jnp.zeros((0,), x.dtype)  # dtype token (JAX-typed residual)


def _bgb_bwd(token, g):
    return (g.astype(token.dtype),)


bf16_grad_barrier.defvjp(_bgb_fwd, _bgb_bwd)


def unembed(params: Dict, x: jax.Array) -> jax.Array:
    """Logits in fp32 via MXU-native bf16 x bf16 -> f32 accumulation.

    Output constrained vocab-sharded over "model": keeps d(embed) gradients
    sharded (otherwise the backward materializes full (V, D) fp32 embedding
    grads on every device — measured ~3.4 GB x several at deepseek scale)."""
    from repro.parallel.constraints import BATCH, constrain
    x = bf16_grad_barrier(x)   # backward residual stream stays bf16
    logits = jnp.einsum("...d,vd->...v", x, params["w"],
                        preferred_element_type=jnp.float32)
    if logits.ndim == 3:
        return constrain(logits, BATCH, None, "model")
    return constrain(logits, BATCH, "model")
