"""GPipe-style pipeline parallelism over a "pipe" mesh axis via shard_map +
collective_permute.

The assigned production meshes have no pipe axis (DP x TP covers them), but
PP is part of the at-scale parallelism portfolio (paper §2, 3D parallelism),
so the framework ships a composable implementation:

  * stage sharding: the layer-stacked params' leading dim is sharded over
    "pipe"; each shard_map instance owns L/P consecutive layers;
  * schedule: GPipe with M microbatches — a lax.scan over M + P - 1 ticks;
    each tick runs every stage on its current microbatch and ppermutes
    activations to the next stage (bubble fraction = (P-1)/(M+P-1));
  * correctness is validated against the unpipelined forward in
    tests/test_pipeline.py.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


PyTree = Any


def pipeline_forward(
    layer_fn: Callable[[PyTree, jax.Array], jax.Array],
    stacked_params: PyTree,          # leaves (L, ...), L % pipe == 0
    x: jax.Array,                    # (M, mb, S, D): M microbatches
    mesh: Mesh,
    *,
    axis: str = "pipe",
) -> jax.Array:
    """Runs x through L layers split across the pipe axis, GPipe schedule.
    Returns (M, mb, S, D)."""
    n_stages = mesh.shape[axis]
    m = x.shape[0]
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def stage(params, xs):
        # params: (L/P, ...) local layers; xs: (M, mb, S, D) with only stage 0
        # feeding real data; others start with zeros and receive via permute.
        stage_id = jax.lax.axis_index(axis)
        n_ticks = m + n_stages - 1

        def run_layers(h):
            def body(h, p):
                return layer_fn(p, h), None
            h, _ = jax.lax.scan(body, h, params)
            return h

        def tick(carry, t):
            outputs, inflight = carry
            # stage 0 injects microbatch t (if any), others use inflight
            inject = jnp.where(t < m, t, 0)
            h_in = jnp.where(stage_id == 0, xs[inject], inflight)
            h_out = run_layers(h_in)
            # last stage records its finished microbatch (t - (P-1))
            out_idx = t - (n_stages - 1)
            do_store = (stage_id == n_stages - 1) & (out_idx >= 0)
            outputs = jax.lax.cond(
                do_store,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, h_out, jnp.maximum(out_idx, 0), 0),
                lambda o: o, outputs)
            # hand activations to the next stage
            inflight = jax.lax.ppermute(h_out, axis, perm)
            return (outputs, inflight), None

        outputs = jnp.zeros_like(xs)
        inflight = jnp.zeros_like(xs[0])
        (outputs, _), _ = jax.lax.scan(tick, (outputs, inflight),
                                       jnp.arange(n_ticks))
        # replicate the last stage's outputs (masked psum = broadcast)
        outputs = jnp.where(stage_id == n_stages - 1, outputs, 0.0)
        return jax.lax.psum(outputs, axis)

    in_specs = (jax.tree.map(lambda _: P(axis), stacked_params,
                             is_leaf=lambda l: hasattr(l, "shape")),
                P())
    fn = jax.shard_map(stage, mesh=mesh, in_specs=in_specs, out_specs=P(),
                       check_vma=False)
    return fn(stacked_params, x)


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
