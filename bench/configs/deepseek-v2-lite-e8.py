"""Plain float32 reference of DeepSeek-V2-Lite (arXiv:2405.04434;
hf:deepseek-ai/DeepSeek-V2-Lite) at one chip's share, as the program's
training loop computes it: leading dense layers, then layers of routed and
shared experts; every layer pre-norm, with multi-head latent attention (no
query compression; keys and values expanded from an RMS-normed 512-wide
latent, and one rotary key of 64 shared by every head, YaRN frequencies and
softmax scale); a final RMSNorm and an untied output head.

The share (the configuration's `deployment`): the layer's 64 experts are
spread over 8 chips and this one holds `experts_held` of them from
`expert_offset`. The router scores all 64 and picks `top_k` a token, its
softmax weights neither renormalised nor rescaled (`norm_topk_prob` false,
`routed_scaling_factor` 1); only the held experts' part of the result is
added, and what the other chips' experts would add is left out, here as in
the program. Here the held experts run densely over every token and are
weighted by the routing (zero where a token did not choose them); the
program runs them over their own rows only. The loss adds DeepSeek's
sequence-wise balance loss over all 64 router probabilities, times
`aux_loss_alpha`.

Departures from the published model, all of them the program's and kept
here so that the two sides compute the same function:
- every RMSNorm stores its scale as an offset from one (`x * (1 + s)`);
- rotary positions rotate the two halves of the 64 rotary dimensions
  (rotate_half) where DeepSeek first de-interleaves them: a fixed
  permutation of the rope columns of random weights;
- the vocabulary is this chip's slice, `vocab_size` rows (12,800 of
  102,400), and the loss's softmax runs over the slice;
- attention is computed dense here, over blocks of queries, and blockwise
  over keys in the program; the same function.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import rms_norm

QUERY_BLOCK = 512
BETA_FAST, BETA_SLOW = 32, 1      # the published rope_scaling's YaRN ramp


def padded_vocab(arch) -> int:
    return -(-arch["vocab_size"] // 256) * 256


def _attn_params(w, a, n):
    """The attention weights of `n` stacked layers (no leading axis if n is
    None)."""
    d, h, r = a["d_model"], a["num_heads"], a["kv_lora_rank"]
    nope, rope, hv = a["qk_nope_head_dim"], a["qk_rope_head_dim"], \
        a["v_head_dim"]
    lead = () if n is None else (n,)
    return {"wq": w(lead + (d, h * (nope + rope)), d ** -0.5),
            "wkv_a": w(lead + (d, r + rope), d ** -0.5),
            "kv_norm": w(lead + (r,), 0.1),
            "wkv_b": w(lead + (r, h * (nope + hv)), r ** -0.5),
            "wo": w(lead + (h * hv, d), (h * hv) ** -0.5)}


def init_params(arch, key):
    """Seeded weights in the program's layout and its dtypes (bfloat16, the
    router float32): normal draws scaled by one over the root of the
    fan-in, the embedding and head at 0.02, norm offsets at 0.1 so that
    their gradients are not all alike."""
    a = arch
    d, f = a["d_model"], a["d_ff"]
    e, n, fe, fs = a["num_experts"], a["experts_held"], a["moe_d_ff"], \
        a["shared_expert_d_ff"]
    n_dense = a["first_k_dense"]
    L = a["num_layers"] - n_dense
    ks = iter(jax.random.split(key, 64))

    def w(shape, scale, dtype=jnp.bfloat16):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dtype)

    dense = [{"ln1": w((d,), 0.1), "ln2": w((d,), 0.1),
              "attn": _attn_params(w, a, None),
              "mlp": {"w_gate": w((d, f), d ** -0.5),
                      "w_up": w((d, f), d ** -0.5),
                      "w_down": w((f, d), f ** -0.5)}}
             for _ in range(n_dense)]
    return {
        "embed": {"w": w((padded_vocab(a), d), 0.02)},
        "lm_head": {"w": w((padded_vocab(a), d), 0.02)},
        "final_norm": w((d,), 0.1),
        "dense": dense,
        "blocks": {
            "ln1": w((L, d), 0.1),
            "ln2": w((L, d), 0.1),
            "attn": _attn_params(w, a, L),
            "moe": {
                "router": w((L, d, e), d ** -0.5, jnp.float32),
                "w_gate": w((L, n, d, fe), d ** -0.5),
                "w_up": w((L, n, d, fe), d ** -0.5),
                "w_down": w((L, n, fe, d), fe ** -0.5),
                "shared": {"w_gate": w((L, d, fs), d ** -0.5),
                           "w_up": w((L, d, fs), d ** -0.5),
                           "w_down": w((L, fs, d), fs ** -0.5)},
            },
        },
    }


def _yarn(arch):
    """YaRN's rotary frequencies over the rope dimensions and its mscale
    (DeepSeek-V2's `DeepseekV2YarnRotaryEmbedding` and attention)."""
    dim, base = arch["qk_rope_head_dim"], arch["rope_theta"]
    factor, orig = arch["yarn_factor"], arch["yarn_original_max_position"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def corr(rot):
        return dim * np.log(orig / (rot * 2 * np.pi)) / (2 * np.log(base))
    low = max(int(np.floor(corr(BETA_FAST))), 0)
    high = min(int(np.ceil(corr(BETA_SLOW))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    freqs = extra / factor * ramp + extra * (1 - ramp)
    mscale = 0.1 * arch["yarn_mscale_all_dim"] * np.log(factor) + 1.0
    return freqs.astype(np.float32), float(mscale)


def _rope(x, freqs):
    """Rotary positions on (B, S, H, dim), halves rotated (rotate_half)."""
    s = x.shape[1]
    ang = np.arange(s, dtype=np.float32)[:, None] * freqs     # (S, dim/2)
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, scale):
    """Causal softmax attention, dense over the keys, a block of queries at
    a time so that the scores of 4,096 positions fit."""
    b, s, h, dq = q.shape
    qb = min(QUERY_BLOCK, s)
    nb = s // qb
    qs = q.reshape(b, nb, qb, h, dq).transpose(1, 0, 2, 3, 4)

    def block(args):
        i, qi = args
        sc = jnp.einsum("bqhd,bkhd->bhqk", qi, k) * scale
        causal = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(s)[None]
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", pr, v)
    out = jax.lax.map(jax.checkpoint(block), (jnp.arange(nb), qs))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, v.shape[-1])


def _mla(x, a, arch, mm):
    b, s, _ = x.shape
    h, r = arch["num_heads"], arch["kv_lora_rank"]
    nope, rope, hv = arch["qk_nope_head_dim"], arch["qk_rope_head_dim"], \
        arch["v_head_dim"]
    freqs, mscale = _yarn(arch)
    q = mm(x, a["wq"]).reshape(b, s, h, nope + rope)
    kv_a = mm(x, a["wkv_a"])
    kv = mm(rms_norm(kv_a[..., :r], a["kv_norm"]), a["wkv_b"]).reshape(
        b, s, h, nope + hv)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], freqs)], -1)
    k_pe = _rope(kv_a[:, :, None, r:], freqs)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (b, s, h, rope))], -1)
    o = _attend(q, k, kv[..., nope:], mscale * mscale / np.sqrt(nope + rope))
    return mm(o.reshape(b, s, h * hv), a["wo"])


def _swiglu(y, m, mm):
    return mm(jax.nn.silu(mm(y, m["w_gate"])) * mm(y, m["w_up"]), m["w_down"])


def _experts(y, m, arch, mm):
    """The held experts' part of the routed output, and the sequence-wise
    balance loss (before alpha)."""
    e, k = arch["num_experts"], arch["top_k"]
    n, off = arch["experts_held"], arch["expert_offset"]
    s = y.shape[1]
    probs = jax.nn.softmax(mm(y, m["router"]), axis=-1)       # (B, S, E)
    top_w, top_e = jax.lax.top_k(probs, k)
    chosen = jax.nn.one_hot(top_e, e)                         # (B, S, k, E)
    weight = jnp.sum(chosen * top_w[..., None], axis=2)[..., off:off + n]

    def expert(out, xs):
        wg, wu, wd, wt = xs
        return out + wt[..., None] * _swiglu(
            y, {"w_gate": wg, "w_up": wu, "w_down": wd}, mm), None
    out, _ = jax.lax.scan(expert, jnp.zeros_like(y),
                          (m["w_gate"], m["w_up"], m["w_down"],
                           jnp.moveaxis(weight, -1, 0)))
    ce = jnp.sum(chosen, axis=(1, 2)) / (s * k / e)           # (B, E)
    aux = jnp.mean(jnp.sum(ce * jnp.mean(probs, axis=1), axis=-1))
    return out, aux


def loss(params, tokens, arch, mm):
    """Mean next-token cross-entropy of `tokens` (B, S+1) over the
    vocabulary slice, plus alpha times the summed balance losses, in
    float32."""
    inp, labels = tokens[:, :-1], tokens[:, 1:]

    def dense(x, p):
        x = x + _mla(rms_norm(x, p["ln1"]), p["attn"], arch, mm)
        return x + _swiglu(rms_norm(x, p["ln2"]), p["mlp"], mm)

    def moe(x, p):
        x = x + _mla(rms_norm(x, p["ln1"]), p["attn"], arch, mm)
        y = rms_norm(x, p["ln2"])
        routed, aux = _experts(y, p["moe"], arch, mm)
        return x + routed + _swiglu(y, p["moe"]["shared"], mm), aux

    x = params["embed"]["w"][inp]
    for p in params["dense"]:
        x = jax.checkpoint(dense)(x, p)
    x, aux = jax.lax.scan(jax.checkpoint(moe), x, params["blocks"])
    x = rms_norm(x, params["final_norm"])
    logits = mm(x, params["lm_head"]["w"].T)
    lab = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    xent = jnp.mean(jax.nn.logsumexp(logits, axis=-1) - lab)
    return xent + arch["aux_loss_alpha"] * jnp.sum(aux)


def flops_per_token(arch, seq_len: int) -> float:
    """Model FLOPs of forward and backward per trained token: 6 per weight
    of every matmul a token uses (latent attention's projections, the dense
    layer's SwiGLU, the router, the shared experts, the held routed experts
    at their expected share, top_k of num_experts choices of which
    experts_held are here, and the head over the vocabulary slice; not the
    embedding lookup), plus causal attention, QK^T over the nope and rope
    dimensions and PV over v's, over the (S+1)/2 keys a query sees on
    average, times three for the backward. Nothing recomputed is
    counted."""
    a = arch
    d, h, r = a["d_model"], a["num_heads"], a["kv_lora_rank"]
    nope, rope, hv = a["qk_nope_head_dim"], a["qk_rope_head_dim"], \
        a["v_head_dim"]
    n_dense = a["first_k_dense"]
    n_moe = a["num_layers"] - n_dense
    mla = d * h * (nope + rope) + d * (r + rope) + r * h * (nope + hv) \
        + h * hv * d
    routed = a["top_k"] * a["experts_held"] / a["num_experts"] \
        * 3 * d * a["moe_d_ff"]
    moe = d * a["num_experts"] + routed + 3 * d * a["shared_expert_d_ff"]
    weights = a["num_layers"] * mla + n_dense * 3 * d * a["d_ff"] \
        + n_moe * moe + a["vocab_size"] * d
    attn = a["num_layers"] * 2 * h * (nope + rope + hv) * (seq_len + 1) / 2
    return 6.0 * weights + 3.0 * attn
