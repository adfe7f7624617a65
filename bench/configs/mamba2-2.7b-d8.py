"""Plain float32 reference of Mamba2-2.7B (arXiv:2405.21060;
state-spaces/mamba2-2.7b) cut to 8 layers, as the program's training loop
computes it: pre-norm Mamba2 blocks (input projections to z, x, B, C and dt;
a causal depthwise convolution with SiLU on x, B and C; the selective state
space scan with one group; a skip D per head; RMSNorm of y * SiLU(z); the
output projection), a final RMSNorm and the embedding tied as the head.

The scan is the chunked state-space-duality algorithm of the paper's
minimal listing, which computes exactly h_t = exp(dt_t A) h_{t-1} +
dt_t B_t x_t, y_t = C_t h_t.

Departures from the published model, all of them the program's and kept
here so that the two sides compute the same function:
- one input projection per stream instead of one fused `in_proj` (the same
  map), and no bias on the convolution (published: `conv_bias=True`);
- every RMSNorm has eps 1e-6 (published 1e-5) and stores its scale as an
  offset from one;
- the embedding has `vocab_size` rounded up to a multiple of 256 rows
  (50,432 for 50,277; published: a multiple of 16, 50,288), and the loss's
  softmax runs over all of them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import rms_norm


def padded_vocab(arch) -> int:
    return -(-arch["vocab_size"] // 256) * 256


def _dims(arch):
    d = arch["d_model"]
    inner = arch["ssm_expand"] * d
    return d, inner, inner // arch["ssm_head_dim"], arch["ssm_state"]


def init_params(arch, key):
    """Seeded weights in the program's layout and dtypes: normal draws
    scaled by one over the root of the fan-in (bfloat16), A from [1, 16] and
    dt from [1e-3, 0.1] log-uniform as the paper initialises them, D at
    one (float32), norm offsets at 0.1."""
    L, k = arch["num_layers"], arch["ssm_conv_kernel"]
    d, inner, h, n = _dims(arch)
    ks = iter(jax.random.split(key, 20))

    def w(shape, scale):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(jnp.bfloat16)

    dt = jnp.exp(jax.random.uniform(next(ks), (L, h), jnp.float32,
                                    np.log(1e-3), np.log(0.1)))
    return {
        "embed": {"w": w((padded_vocab(arch), d), 0.02)},
        "final_norm": w((d,), 0.1),
        "blocks": {
            "ln1": w((L, d), 0.1),
            "mamba": {
                "w_x": w((L, d, inner), d ** -0.5),
                "w_z": w((L, d, inner), d ** -0.5),
                "w_b": w((L, d, n), d ** -0.5),
                "w_c": w((L, d, n), d ** -0.5),
                "w_dt": w((L, d, h), d ** -0.5),
                "conv_x": w((L, inner, k), k ** -0.5),
                "conv_b": w((L, n, k), k ** -0.5),
                "conv_c": w((L, n, k), k ** -0.5),
                "a_log": jnp.log(jax.random.uniform(
                    next(ks), (L, h), jnp.float32, 1.0, 16.0)),
                "d_skip": jnp.ones((L, h), jnp.float32),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "norm": w((L, inner), 0.1),
                "out": w((L, inner, d), inner ** -0.5),
            },
        },
    }


def _conv(x, w):
    """Causal depthwise convolution over time, then SiLU. x (B, S, C),
    w (C, k): out_t = sum_i w[:, i] * x_{t-(k-1-i)}."""
    k = w.shape[-1]
    out = sum(jnp.pad(x, ((0, 0), (k - 1 - i, 0), (0, 0)))[:, :x.shape[1]]
              * w[:, i] for i in range(k))
    return jax.nn.silu(out)


def _segsum(x):
    """(..., T) -> (..., T, T): sum of x over (j, i], -inf above the
    diagonal."""
    t = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    ss = cs[..., :, None] - cs[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), ss, -jnp.inf)


def _ssd(x, a, b, c, chunk: int):
    """y of the scan h_t = exp(a_t) h_{t-1} + b_t x_t, y_t = c_t h_t from a
    zero state. x (B, S, H, P) already times dt, a (B, S, H) = dt * A,
    b and c (B, S, N) shared by every head."""
    bs, s, h, p = x.shape
    nc = s // chunk
    x = x.reshape(bs, nc, chunk, h, p)
    b = b.reshape(bs, nc, chunk, -1)
    c = c.reshape(bs, nc, chunk, -1)
    a = a.reshape(bs, nc, chunk, h).transpose(0, 3, 1, 2)      # B H C L
    a_cs = jnp.cumsum(a, axis=-1)
    y_diag = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp", c, b,
                        jnp.exp(_segsum(a)), x)
    decay = jnp.exp(a_cs[..., -1:] - a_cs)
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", b, decay, x)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    chunk_decay = jnp.exp(_segsum(jnp.pad(a_cs[..., -1],
                                          ((0, 0), (0, 0), (1, 0)))))
    states = jnp.einsum("bhzc,bchpn->bzhpn", chunk_decay, states)[:, :-1]
    y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", c, states, jnp.exp(a_cs))
    return (y_diag + y_off).reshape(bs, s, h, p)


def loss(params, tokens, arch, mm):
    """Mean next-token cross-entropy of `tokens` (B, S+1), in float32."""
    d, inner, h, n = _dims(arch)
    pdim = arch["ssm_head_dim"]
    inp, labels = tokens[:, :-1], tokens[:, 1:]
    bs, s = inp.shape

    def layer(x, p):
        m = p["mamba"]
        u = rms_norm(x, p["ln1"])
        z = mm(u, m["w_z"])
        xs = _conv(mm(u, m["w_x"]), m["conv_x"]).reshape(bs, s, h, pdim)
        bm = _conv(mm(u, m["w_b"]), m["conv_b"])
        cm = _conv(mm(u, m["w_c"]), m["conv_c"])
        dt = jax.nn.softplus(mm(u, m["w_dt"]) + m["dt_bias"])
        y = _ssd(xs * dt[..., None], dt * -jnp.exp(m["a_log"]), bm, cm,
                 arch["ssm_chunk"])
        y = (y + m["d_skip"][:, None] * xs).reshape(bs, s, inner)
        y = rms_norm(y * jax.nn.silu(z), m["norm"])
        return x + mm(y, m["out"]), None

    x = params["embed"]["w"][inp]
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["blocks"])
    x = rms_norm(x, params["final_norm"])
    logits = mm(x, params["embed"]["w"].T)
    lab = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - lab)


def flops_per_token(arch, seq_len: int) -> float:
    """Model FLOPs of forward and backward per trained token: 6 per weight
    of every matmul (input and output projections, the tied head over the
    published vocabulary; not the embedding lookup), plus, times three for
    the backward, the convolution (2 k per channel) and the chunked scan of
    the paper's algorithm with chunk Lc: C B^T over the (Lc+1)/2 earlier
    positions of a chunk (one group), their weighted sum of x per head, and
    per head the inter-chunk read C h and state update B x (2 N P each).
    Nothing recomputed is counted."""
    L, k, lc = arch["num_layers"], arch["ssm_conv_kernel"], arch["ssm_chunk"]
    d, inner, h, n = _dims(arch)
    p = arch["ssm_head_dim"]
    weights = L * (d * (2 * inner + 2 * n + h) + inner * d) \
        + arch["vocab_size"] * d
    seen = (min(lc, seq_len) + 1) / 2
    scan = 2 * n * seen + h * (2 * p * seen + 2 * 2 * n * p)
    conv = 2 * k * (inner + 2 * n)
    return 6.0 * weights + 3.0 * L * (scan + conv)
