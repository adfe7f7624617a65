"""Instant checkpointing: neighboring redundancy (paper §4.2, Fig. 3 (B)).

Each iteration, every device streams its *unique* state shard to the next
worker in the DP ring via ``lax.ppermute`` (TPU collective-permute — the
ICI-native point-to-point the paper's RDMA write maps onto). The permute is
fused into the compiled train step so XLA overlaps it with backward/update
compute: this is the "use idle links during compute" mechanism, and the FCR
condition (core/fcr.py) says when it hides completely.

The permuted shards come back as a step *output*; the host runtime
(repro.runtime) keeps them in host RAM as the neighbor's live checkpoint.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


PyTree = Any


def ring_perm(n: int, shift: int = 1):
    return [(i, (i + shift) % n) for i in range(n)]


def neighbor_backup(tree: PyTree, pspecs: PyTree, mesh: Mesh,
                    *, axis: str = "data", shift: int = 1) -> PyTree:
    """Permute every leaf one step along the DP ring. Call inside jit.

    tree/pspecs may contain None leaves (razor-redundant): they pass through
    untouched and cost no ICI traffic.
    """
    n = mesh.shape[axis]
    if n <= 1:
        return tree
    perm = ring_perm(n, shift)

    is_p = lambda x: isinstance(x, P) or x is None
    flat_specs, treedef = jax.tree_util.tree_flatten(pspecs, is_leaf=is_p)
    flat_vals = treedef.flatten_up_to(tree)

    present = [(i, v, s) for i, (v, s) in enumerate(zip(flat_vals, flat_specs))
               if v is not None]
    if not present:
        return tree
    idxs, vals, specs = zip(*present)

    def permute_all(*xs):
        return tuple(jax.lax.ppermute(x, axis, perm) for x in xs)

    out = jax.shard_map(
        permute_all, mesh=mesh,
        in_specs=tuple(specs), out_specs=tuple(specs), check_vma=False,
    )(*vals)

    new_flat = list(flat_vals)
    for i, o in zip(idxs, out):
        new_flat[i] = o
    return jax.tree_util.tree_unflatten(treedef, new_flat)
