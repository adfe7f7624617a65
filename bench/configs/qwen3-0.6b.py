"""Plain float32 reference of Qwen3-0.6B (hf:Qwen/Qwen3-0.6B), as the
program's training loop computes it: pre-norm decoder layers of grouped-query
attention with per-head RMSNorm on queries and keys and rotary positions,
a SwiGLU MLP, a final RMSNorm and the embedding tied as the output head.

Departures from the published model, all of them the program's and kept
here so that the two sides compute the same function:
- every RMSNorm stores its scale as an offset from one (`x * (1 + s)`);
- the embedding has `vocab_size` rounded up to a multiple of 256 rows
  (152,064 for 151,936), and the loss's softmax runs over all of them;
- attention is computed dense here and blockwise in the program; the same
  function.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import rms_norm


def padded_vocab(arch) -> int:
    return -(-arch["vocab_size"] // 256) * 256


def init_params(arch, key):
    """Seeded weights in the program's layout and its bfloat16: normal
    draws scaled by one over the root of the fan-in, the embedding at 0.02,
    norm offsets at 0.1 so that their gradients are not all alike."""
    L, d, f = arch["num_layers"], arch["d_model"], arch["d_ff"]
    h, kh, hd = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    ks = iter(jax.random.split(key, 16))

    def w(shape, scale):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(jnp.bfloat16)

    return {
        "embed": {"w": w((padded_vocab(arch), d), 0.02)},
        "final_norm": w((d,), 0.1),
        "blocks": {
            "ln1": w((L, d), 0.1),
            "ln2": w((L, d), 0.1),
            "attn": {
                "wq": w((L, d, h * hd), d ** -0.5),
                "wk": w((L, d, kh * hd), d ** -0.5),
                "wv": w((L, d, kh * hd), d ** -0.5),
                "wo": w((L, h * hd, d), (h * hd) ** -0.5),
                "q_norm": w((L, hd), 0.1),
                "k_norm": w((L, hd), 0.1),
            },
            "mlp": {
                "w_gate": w((L, d, f), d ** -0.5),
                "w_up": w((L, d, f), d ** -0.5),
                "w_down": w((L, f, d), f ** -0.5),
            },
        },
    }


def _rope(x, theta: float):
    """Rotary positions on (B, S, H, hd), halves rotated (rotate_half)."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = np.arange(s, dtype=np.float32)[:, None] * freqs     # (S, hd/2)
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss(params, tokens, arch, mm):
    """Mean next-token cross-entropy of `tokens` (B, S+1), in float32."""
    h, kh, hd = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    inp, labels = tokens[:, :-1], tokens[:, 1:]
    b, s = inp.shape
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        a = p["attn"]
        y = rms_norm(x, p["ln1"])
        q = mm(y, a["wq"]).reshape(b, s, h, hd)
        k = mm(y, a["wk"]).reshape(b, s, kh, hd)
        v = mm(y, a["wv"]).reshape(b, s, kh, hd)
        q = _rope(rms_norm(q, a["q_norm"]), arch["rope_theta"])
        k = _rope(rms_norm(k, a["k_norm"]), arch["rope_theta"])
        k = jnp.repeat(k, h // kh, axis=2)
        v = jnp.repeat(v, h // kh, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", pr, v).reshape(b, s, h * hd)
        x = x + mm(o, a["wo"])
        y = rms_norm(x, p["ln2"])
        m = p["mlp"]
        return x + mm(jax.nn.silu(mm(y, m["w_gate"])) * mm(y, m["w_up"]),
                      m["w_down"]), None

    x = params["embed"]["w"][inp]
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["blocks"])
    x = rms_norm(x, params["final_norm"])
    logits = mm(x, params["embed"]["w"].T)
    lab = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - lab)


def flops_per_token(arch, seq_len: int) -> float:
    """Model FLOPs of forward and backward per trained token: 6 per weight
    of every matmul (projections, MLP, the tied output head over the
    published vocabulary; not the embedding lookup), plus causal attention,
    QK^T and PV over the (S+1)/2 keys a query sees on average, times three
    for the backward. Nothing recomputed is counted."""
    L, d, f = arch["num_layers"], arch["d_model"], arch["d_ff"]
    h, kh, hd = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    weights = L * (d * h * hd + 2 * d * kh * hd + h * hd * d + 3 * d * f) \
        + arch["vocab_size"] * d
    attn = L * 2 * 2 * h * hd * (seq_len + 1) / 2
    return 6.0 * weights + 3.0 * attn
