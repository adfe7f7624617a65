"""Best-effort activation sharding constraints.

Model code is mesh-agnostic: ``constrain`` applies
``jax.lax.with_sharding_constraint`` against the ambient mesh when one is
active and silently no-ops otherwise (single-device smoke tests, kernels).
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

BATCH = ("pod", "data")  # logical batch axes (pod may be absent)


def _mesh_shape():
    """Usable (non-Manual) mesh axes -> sizes in the current trace context."""
    try:
        am = jax.sharding.get_abstract_mesh()
        if am is not None and not am.empty:
            types = getattr(am, "axis_types", None) or ()
            out = {}
            for i, (n, s) in enumerate(zip(am.axis_names, am.axis_sizes)):
                if types and str(types[i]) == "Manual":
                    continue  # inside shard_map: manual axes are off-limits
                out[n] = s
            return out
        from jax._src import mesh as mesh_lib
        m = mesh_lib.thread_resources.env.physical_mesh
        if m.empty:
            return {}
        return {n: s for n, s in zip(m.axis_names, m.devices.shape)}
    except Exception:
        return {}


def _fit(mesh, dim: int, names):
    """The axes of `names` that cut `dim` evenly, in order."""
    kept, div = [], 1
    for a in names:
        sz = mesh.get(a)
        if sz and dim % (div * sz) == 0:
            kept.append(a)
            div *= sz
    return kept


def expert_mesh(batch: int, experts: int):
    """For a `shard_map` of an expert layer: the ambient mesh, the batch
    axes that cut a leading dim of `batch`, and "model" if it cuts the
    `experts` held (else None); (None, (), None) when neither cuts."""
    mesh = {a: n for a, n in _mesh_shape().items() if n > 1}
    kept = _fit(mesh, batch, BATCH)
    ep = "model" if _fit(mesh, experts, ("model",)) else None
    if not kept and ep is None:
        return None, (), None
    am = jax.sharding.get_abstract_mesh()
    if am is None or am.empty:
        from jax._src import mesh as mesh_lib
        am = mesh_lib.thread_resources.env.physical_mesh
    return am, tuple(kept), ep


def constrain(x, *parts):
    """constrain(x, ("pod","data"), "model", None) — axes missing from the
    ambient mesh are dropped; axes that don't divide the dim are dropped;
    no mesh means no-op."""
    try:
        mesh = _mesh_shape()
        if not mesh:
            return x
        fixed = []
        for dim, p in zip(x.shape, parts):
            if p is None:
                fixed.append(None)
                continue
            kept = _fit(mesh, dim, p if isinstance(p, (tuple, list)) else (p,))
            fixed.append(tuple(kept) if len(kept) > 1
                         else (kept[0] if kept else None))
        return jax.lax.with_sharding_constraint(x, P(*fixed))
    except Exception:
        return x
