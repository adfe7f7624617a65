"""Mixture-of-Experts at one chip's share of an expert-parallel layer.

The router scores every expert and picks `top_k` a token. The layer holds
the experts `[expert_offset, expert_offset + held_experts)` and computes
their part of the result for the tokens routed to them: the (token, choice)
rows are sorted by held expert, and each expert's SwiGLU runs as one
grouped matmul over its own rows (`jax.lax.ragged_dot`). So no
token is dropped however skewed the routing, and the work follows the rows
routed here, not a worst-case pad. What experts held on other chips would
add is left out: on one chip the layer runs without its exchange. An uncut
layer holds every expert (padded to `padded_experts`, so that EP-16 divides
the stack; no token is routed to a pad).

On a mesh the layer runs in a `shard_map`. Each shard of the batch sorts
and multiplies its own rows, so no row crosses the data axis (a global
sort there cost about 29 s a step of collectives at qwen3-moe train_4k
scale in the production dry run). Each shard of the `model` axis holds
its slice of the experts and computes their part, as one chip of an
expert-parallel layer does, and the parts are summed over `model`.

Shared experts run on every token, with Qwen2-MoE's sigmoid gate or none
(DeepSeek). The balance loss is DeepSeek's sequence-wise one (`seq_aux`) or
the Switch Transformer's.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.parallel.constraints import BATCH, constrain, expert_mesh


def moe_init(key, cfg, dtype) -> Dict:
    e, n = cfg.num_experts, cfg.held_experts
    d, f = cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 6)
    sc, so = 1.0 / np.sqrt(d), 1.0 / np.sqrt(f)
    p = {
        "router": (jax.random.normal(ks[0], (d, e)) * sc).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (n, d, f)) * sc).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (n, d, f)) * sc).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (n, f, d)) * so).astype(dtype),
    }
    if cfg.num_shared_experts:
        fs = cfg.shared_expert_d_ff
        p["shared"] = {
            "w_gate": (jax.random.normal(ks[4], (d, fs)) * sc).astype(dtype),
            "w_up": (jax.random.normal(ks[5], (d, fs)) * sc).astype(dtype),
            "w_down": (jax.random.normal(
                jax.random.fold_in(ks[5], 1), (fs, d)) / np.sqrt(fs)).astype(dtype),
        }
        if cfg.shared_expert_gate:
            p["shared_gate"] = jnp.zeros((d,), jnp.float32)
    return p


def route(p: Dict, cfg, x: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, D) -> the chosen experts' weights and ids, each (B, S, k),
    and the balance loss (before `aux_loss_alpha`). The router runs in
    float32 over every expert."""
    e, k = cfg.num_experts, cfg.top_k
    logits = jnp.matmul(x.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)                     # (B, S, E)
    top_w, top_e = jax.lax.top_k(probs, k)
    if cfg.norm_topk_prob:
        top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)
    if cfg.seq_aux:
        # each sequence's selections of e over the S * k / E an even split
        # gives, times its mean probability of e (DeepSeek-V2)
        s = x.shape[1]
        ce = jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.float32), axis=(1, 2))
        ce = ce / (s * k / e)
        aux = jnp.mean(jnp.sum(ce * jnp.mean(probs, axis=1), axis=-1))
    else:
        # Switch Transformer: top-1 share of tokens times mean probability
        hard = jax.nn.one_hot(jnp.argmax(probs, -1), e, dtype=jnp.float32)
        aux = e * jnp.sum(
            jnp.mean(hard, axis=(0, 1)) * jnp.mean(probs, axis=(0, 1)))
    return top_w, top_e, aux


def _held_part(cfg, x, top_w, top_e, w_gate, w_up, w_down, offset):
    """One batch shard's tokens x: (T, D) with their choices (T, k) -> what
    the experts `[offset, offset + n)` of the stacks (n, ...) add to each
    token: (T, D) float32."""
    t, d = x.shape
    k, n = cfg.top_k, w_gate.shape[0]
    with jax.named_scope("moe.route"):
        local = top_e.reshape(t * k) - offset
        held = (local >= 0) & (local < n)
        # rows of held experts first, grouped by expert; the rest after
        group = jnp.where(held, local, n)
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=n + 1)[:n].astype(jnp.int32)
        row_tok, row_held = order // k, held[order]

    with jax.named_scope("moe.experts"):
        # rows past the held groups are zeroed going in, and their
        # cotangent going back, whatever the grouped matmul leaves there
        xs = jnp.where(row_held[:, None], x[row_tok], 0)
        g = jax.lax.ragged_dot(xs, w_gate, sizes)
        u = jax.lax.ragged_dot(xs, w_up, sizes)
        y = jax.lax.ragged_dot(jax.nn.silu(g) * u, w_down, sizes)
        y = jnp.where(row_held[:, None], y, 0)
        back = jnp.zeros((t * k,), jnp.int32).at[order].set(
            jnp.arange(t * k, dtype=jnp.int32), unique_indices=True)
        y = y.at[back].get(unique_indices=True).reshape(t, k, d)
        w = jnp.where(held.reshape(t, k), top_w.reshape(t, k), 0.0)
        return jnp.sum(y.astype(jnp.float32) * w[..., None], axis=1)


def moe_apply(p: Dict, cfg, x: jax.Array):
    """x: (B, S, D) -> (out: (B, S, D), aux_loss: scalar)."""
    b, s, d = x.shape
    x = constrain(x, BATCH, None, None)
    with jax.named_scope("moe.route"):
        top_w, top_e, aux = route(p, cfg, x)
    experts = (p["w_gate"], p["w_up"], p["w_down"])
    mesh, axes, ep = expert_mesh(b, cfg.held_experts)

    def part(x, top_w, top_e, *experts):
        offset = cfg.expert_offset
        if ep:
            offset = offset + jax.lax.axis_index(ep) * experts[0].shape[0]
        out = _held_part(cfg, x.reshape(-1, d), top_w, top_e, *experts,
                         offset)
        return jax.lax.psum(out, ep) if ep else out

    if mesh is None:
        out = part(x, top_w, top_e, *experts)
    else:
        rows = P(axes or None)
        out = jax.shard_map(
            lambda *a: part(*a)[None], mesh=mesh,
            in_specs=(rows,) * 3 + (P(ep),) * 3, out_specs=rows,
            axis_names=set(axes) | ({ep} if ep else set()), check_vma=False,
        )(x, top_w, top_e, *experts).reshape(b * s, d)

    if "shared" in p:
        with jax.named_scope("moe.shared"):
            xf = x.reshape(b * s, d)
            sp = p["shared"]
            sg = jax.nn.silu(xf @ sp["w_gate"]) * (xf @ sp["w_up"])
            shared_out = (sg @ sp["w_down"]).astype(jnp.float32)
            if "shared_gate" in p:
                shared_out = shared_out * jax.nn.sigmoid(
                    xf.astype(jnp.float32) @ p["shared_gate"][:, None])
            out = out + shared_out

    return out.reshape(b, s, d).astype(x.dtype), aux
