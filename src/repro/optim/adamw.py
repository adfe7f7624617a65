"""AdamW with ZeRO-1 layout: fp32 master + m + v, all sharded over the "data"
mesh axis (specs from ``repro.parallel.sharding.zero_pspecs``). bf16 params are
re-materialized from the master after each update (XLA turns the sharding
mismatch into reduce-scatter(grads) + all-gather(params) — ZeRO-1's exact
communication pattern, derived from sharding constraints alone).

The razor arithmetic depends on this layout: unique state per device is
master+m+v = 12·φ/d bytes (paper §4.2).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

PyTree = Any


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000


def adamw_init(params: PyTree) -> Dict[str, PyTree]:
    # a copy even when params are already fp32: the train step donates the
    # state, and XLA refuses to donate one buffer twice
    f32 = lambda p: jnp.array(p, jnp.float32)
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "master": jax.tree.map(f32, params),
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
    }


def global_norm(tree: PyTree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def adamw_update(
    grads: PyTree,
    opt: Dict[str, PyTree],
    step: jax.Array,
    hp: AdamWConfig,
    lr: jax.Array,
) -> Tuple[PyTree, Dict[str, PyTree]]:
    """Returns (new_params_bf16_source=master, new_opt). Caller casts params."""
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, hp.grad_clip / jnp.maximum(gnorm, 1e-9))
    t = (step + 1).astype(jnp.float32)
    bc1 = 1.0 - hp.b1 ** t
    bc2 = 1.0 - hp.b2 ** t

    def upd(g, master, m, v):
        g = g.astype(jnp.float32) * scale
        m = hp.b1 * m + (1.0 - hp.b1) * g
        v = hp.b2 * v + (1.0 - hp.b2) * jnp.square(g)
        update = (m / bc1) / (jnp.sqrt(v / bc2) + hp.eps)
        master = master - lr * (update + hp.weight_decay * master)
        return master, m, v

    out = jax.tree.map(upd, grads, opt["master"], opt["m"], opt["v"])
    new_master = jax.tree.map(lambda x: x[0], out,
                              is_leaf=lambda x: isinstance(x, tuple))
    new_m = jax.tree.map(lambda x: x[1], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    new_v = jax.tree.map(lambda x: x[2], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    return new_master, {"master": new_master, "m": new_m, "v": new_v}


def cast_params(master: PyTree, like: PyTree) -> PyTree:
    return jax.tree.map(lambda m, p: m.astype(p.dtype), master, like)
