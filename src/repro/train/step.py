"""The train step: one update body and its two wrappers.

``update_body(model, hp)`` is the step's math, un-jitted: the loss and its
gradient, the cosine schedule, the AdamW update and the cast back to the
parameters' dtype. Two wrappers jit it:

  * ``loop_step`` on one device, the failover loop's step (runtime/cluster.py):

        new_state, loss = step(state, batch)

  * ``build_train_step`` on a mesh, an SPMD step with the paper's instant
    checkpoint fused in:

        new_state, metrics, backup = step(state, batch)

    ``backup`` is the ZeRO-unique optimizer shard permuted one hop along the
    DP ring (core/instant.py), an explicit collective-permute in the compiled
    HLO that XLA overlaps with compute. ``backup`` leaves are None when
    instant checkpointing is disabled or the leaf is razor-redundant.

The module also holds, until it moves to the simulator, the wire accounting
of one iteration (``TrafficProfile`` and the functions that build and submit
it).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.instant import neighbor_backup
from repro.core.razor import RazorPlan, razor_plan
from repro.models import Model
from repro.models.modes import fsdp_unshard
from repro.optim import AdamWConfig, adamw_update, cast_params, cosine_schedule
from repro.parallel import sharding as shd
from repro.train.state import StatePlan, make_state_plan

PyTree = Any

# the mesh axis the ZeRO shards and the instant checkpoint's ring run along
DATA_AXIS = "data"


def update_body(model: Model, hp: AdamWConfig) -> Callable:
    """The un-jitted ``(state, batch) -> (new_state, loss, aux, lr)``."""

    def forward(p, batch):
        with jax.named_scope("forward"):
            return model.loss(p, batch)

    def body(state, batch):
        # the backward's ops carry the forward's scope under `transpose`
        (loss, aux), grads = jax.value_and_grad(
            forward, has_aux=True)(state["params"], batch)
        with jax.named_scope("optimizer"):
            lr = cosine_schedule(state["step"], lr=hp.lr,
                                 warmup_steps=hp.warmup_steps,
                                 total_steps=hp.total_steps)
            _, new_opt = adamw_update(grads, state["opt"], state["step"], hp,
                                      lr)
            new_params = cast_params(new_opt["master"], state["params"])
        return ({"step": state["step"] + 1, "params": new_params,
                 "opt": new_opt}, loss, aux, lr)

    return body


def loop_step(model: Model, hp: AdamWConfig) -> Callable:
    """The loop's jitted train step, ``(state, batch) -> (state, loss)``,
    on one device. The state is donated: the step updates it in place, so
    at published width one copy of the state (not two) is resident."""
    body = update_body(model, hp)

    def step(state, batch):
        new_state, loss, _, _ = body(state, batch)
        return new_state, loss

    return jax.jit(step, donate_argnums=(0,))


@dataclass(frozen=True)
class StepArtifacts:
    step_fn: Callable            # jitted
    plan: StatePlan
    razor: RazorPlan
    input_pspecs: PyTree
    backup_pspecs: PyTree        # None-leaved pytree matching backup output


def build_train_step(
    model: Model,
    mesh: Mesh,
    hp: AdamWConfig = AdamWConfig(),
    *,
    instant_ckpt: bool = True,
    donate: bool = True,
    shape=None,
) -> StepArtifacts:
    """The update body on `mesh`: FSDP-stored parameters, ZeRO-sharded
    optimizer state and, with `instant_ckpt`, the razor-unique optimizer
    shard sent one hop along the data axis as ``backup``."""
    cfg = model.cfg
    plan = make_state_plan(model, mesh, fsdp_params=True)
    razor = razor_plan(plan.state_specs["opt"], plan.opt_pspecs,
                       plan.state_specs["params"], mesh, zero_axis=DATA_AXIS)

    # backup = unique opt leaves only (razor) when instant ckpt is on
    if instant_ckpt and mesh.shape.get(DATA_AXIS, 1) > 1:
        backup_pspecs = jax.tree.map(
            lambda ps, m: ps if m else None, plan.opt_pspecs, razor.unique_mask,
            is_leaf=lambda x: isinstance(x, P))
    else:
        backup_pspecs = jax.tree.map(lambda ps: None, plan.opt_pspecs,
                                     is_leaf=lambda x: isinstance(x, P))

    input_pspecs = shd.input_pspecs(cfg, model.input_specs(shape), mesh) \
        if shape else None
    body = update_body(model, hp)

    def train_step(state, batch):
        with fsdp_unshard():
            new_state, loss, aux, lr = body(state, batch)
            backup = _mask(new_state["opt"], backup_pspecs)
            backup = neighbor_backup(backup, backup_pspecs, mesh,
                                     axis=DATA_AXIS)
        return new_state, {"loss": loss, **aux, "lr": lr}, backup

    metrics_shard = None  # replicated scalars; let XLA infer
    backup_shardings = jax.tree.map(
        lambda ps: NamedSharding(mesh, ps) if ps is not None else None,
        backup_pspecs, is_leaf=lambda x: isinstance(x, P) or x is None)

    jit_kwargs: Dict[str, Any] = dict(
        in_shardings=(shd.to_named(plan.state_pspecs, mesh),
                      shd.to_named(input_pspecs, mesh) if input_pspecs else None),
        out_shardings=(shd.to_named(plan.state_pspecs, mesh),
                       metrics_shard, backup_shardings),
    )
    if donate:
        jit_kwargs["donate_argnums"] = (0,)
    step_fn = jax.jit(train_step, **jit_kwargs)
    return StepArtifacts(step_fn, plan, razor, input_pspecs, backup_pspecs)


def _mask(tree: PyTree, mask_pspecs: PyTree) -> PyTree:
    is_p = lambda x: isinstance(x, P) or x is None
    return jax.tree.map(lambda ps, x: None if ps is None else x,
                        mask_pspecs, tree, is_leaf=is_p)


# --------------------------------------------------------------------------- #
# Link-traffic accounting (paper §5.3): what one training iteration puts on
# the wire, per worker (all volumes in bytes). The runtime submits
# `train_bytes` as TRAIN traffic to the StateStream transport — the volume
# that preempts checkpoint chunks — while the instant-ckpt shard rides the
# fabric as STATE. On a hierarchical PodFabric the allreduce is two-level
# (intra-pod ring + inter-pod gateway ring), so the profile carries a
# per-tier wire volume.
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class TrafficProfile:
    train_bytes: float   # per-ICI-edge gradient allreduce volume (preempting)
    state_bytes: float   # razor-unique instant-ckpt shard, one DP-ring hop
    dcn_bytes: float = 0.0  # per-DCN-edge inter-pod allreduce volume


def step_traffic(grad_bytes: float, dp: int,
                 razor: Optional[RazorPlan] = None,
                 state_bytes: Optional[float] = None) -> TrafficProfile:
    """Per-iteration wire volumes for one worker (flat DP ring). Ring
    allreduce moves 2(dp-1)/dp of the gradient bytes; the instant checkpoint
    moves the razor-unique optimizer shard one hop along the DP ring."""
    wire = 2.0 * (dp - 1) / dp * grad_bytes if dp > 1 else 0.0
    if state_bytes is None:
        state_bytes = float(razor.unique_bytes_per_device_ring) if razor \
            else 0.0
    return TrafficProfile(wire, state_bytes)


def hierarchical_step_traffic(grad_bytes: float, n_pods: int, pod_size: int,
                              razor: Optional[RazorPlan] = None,
                              state_bytes: Optional[float] = None
                              ) -> TrafficProfile:
    """Per-iteration wire volumes for the two-level allreduce on a
    `PodFabric` (bytes).

    Intra-pod: ring reduce-scatter + allgather over the `pod_size`-node ICI
    ring moves ``2(s-1)/s * grad_bytes`` across every ICI edge
    (`train_bytes`). Inter-pod: after the reduce-scatter each node holds a
    ``grad_bytes / s`` shard; the gateways allreduce those shards around the
    `n_pods`-pod DCN ring, putting ``2(P-1)/P * grad_bytes / s`` on every
    DCN edge (`dcn_bytes`). Degenerates to `step_traffic` shapes when
    P == 1 (no DCN leg) or s == 1 (pure DCN ring of gateways)."""
    s, p = pod_size, n_pods
    ici = 2.0 * (s - 1) / s * grad_bytes if s > 1 else 0.0
    shard = grad_bytes / max(s, 1)
    dcn = 2.0 * (p - 1) / p * shard if p > 1 else 0.0
    if state_bytes is None:
        state_bytes = float(razor.unique_bytes_per_device_ring) if razor \
            else 0.0
    return TrafficProfile(ici, state_bytes, dcn)


def submit_step_traffic(transport, profile: TrafficProfile, t: float):
    """Put one iteration's allreduce volume on the fabric, edge by edge.

    A ring allreduce moves 2(n-1) messages of S/n bytes across EVERY ring
    edge, so the per-edge wire volume equals the per-worker volume
    (`profile.train_bytes`) — on a `TopologyTransport` this loads each live
    ring edge with exactly that, and checkpoint STATE chunks then contend
    per-edge; on a single-link transport it degrades to the global
    submission. A profile with a `dcn_bytes` leg (hierarchical allreduce)
    loads each tier with its own volume instead. Returns the submitted
    transfer(s)."""
    if profile.dcn_bytes and hasattr(transport, "submit_train_tiers"):
        from repro.core.lccl import TIER_DCN, TIER_ICI
        return transport.submit_train_tiers(
            {TIER_ICI: profile.train_bytes, TIER_DCN: profile.dcn_bytes}, t)
    return transport.submit_train(profile.train_bytes, t)
