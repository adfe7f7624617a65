"""First-principles per-device HBM traffic model for the memory roofline term.

Why a model: XLA:CPU's post-compile "bytes accessed" reflects CPU fusion
decisions (orders of magnitude above TPU reality for fused attention/loss
graphs), so the memory term is derived from the workload itself:

  * parameter / optimizer / cache bytes are EXACT per-device values computed
    from the ShapeDtypeStructs and their PartitionSpecs;
  * activation streams are counted as tensor passes over the residual stream
    and block-local intermediates (weight-stationary execution, flash-style
    attention with no score materialization), with remat re-reads included.

The measured XLA number is still recorded in the dry-run JSON for reference.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import numpy as np
from jax.sharding import Mesh

from repro.configs import ArchConfig, ShapeConfig

PyTree = Any


def _spec_div(pspec, mesh: Mesh) -> int:
    div = 1
    for part in pspec:
        if part is None:
            continue
        parts = part if isinstance(part, (tuple, list)) else (part,)
        for a in parts:
            div *= mesh.shape[a]
    return div


def sharded_bytes(specs: PyTree, pspecs: PyTree, mesh: Mesh) -> int:
    """Exact per-device bytes of a sharded pytree."""
    leaves, treedef = jax.tree_util.tree_flatten(specs)
    ps_leaves = treedef.flatten_up_to(pspecs)
    total = 0
    for leaf, ps in zip(leaves, ps_leaves):
        n = int(np.prod(leaf.shape)) * jax.dtypes.canonicalize_dtype(
            leaf.dtype).itemsize
        total += n // max(_spec_div(ps, mesh), 1) if ps is not None else n
    return total


def _activation_traffic(cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh,
                        *, train: bool) -> float:
    """Per-device activation HBM bytes for one full forward (+backward)."""
    dp = int(np.prod([mesh.shape[a] for a in ("pod", "data")
                      if a in mesh.axis_names]))
    tp = mesh.shape.get("model", 1)
    b, s = shape.global_batch, shape.seq_len
    t_loc = b * s / dp                      # tokens per device
    d = cfg.d_model
    bt = 2.0                                # bf16

    def shard(n, k):                        # shard dim n over tp if divisible
        return n / tp if (n % tp == 0 and n >= tp) else n

    passes = 0.0
    l = cfg.num_layers
    if cfg.family in ("dense", "moe", "vlm", "encdec"):
        hd = cfg.resolved_head_dim
        qkv = shard(cfg.num_heads, tp) * hd + 2 * shard(cfg.num_kv_heads, tp) * hd
        # residual x: read by ln1/ln2 + written by attn/mlp adds (4 passes)
        per_layer = 4 * d
        # attention: q/k/v write+read, flash kv re-read per q block, out
        n_kv_blocks = max(s // 1024, 1)
        per_layer += 2 * qkv + 2 * shard(cfg.num_kv_heads, tp) * hd * n_kv_blocks \
            + 2 * shard(cfg.num_heads, tp) * hd
        if cfg.is_moe:
            fe = cfg.moe_d_ff
            # dropless bound: each token's top_k rows of the sorted buffer
            # (rows, D) write+read + expert h (rows, Fe) w+r + out
            per_layer += cfg.top_k * (4 * d + 4 * fe)
            if cfg.num_shared_experts:
                per_layer += 4 * shard(cfg.shared_expert_d_ff, tp) + 2 * d
        else:
            per_layer += 4 * shard(cfg.d_ff, tp) + 2 * d
        passes = l * per_layer
        if cfg.family == "encdec":
            # encoder (same block shape, seq = encoder_seq) + cross-attention
            enc_t_loc = b * cfg.encoder_seq / dp
            passes += cfg.encoder_layers * (4 * d + 2 * qkv + 4 *
                                            shard(cfg.d_ff, tp) + 2 * d) \
                * (enc_t_loc / t_loc)
            passes += l * (2 * qkv + 2 * d)          # cross attn streams
    elif cfg.family in ("ssm", "hybrid"):
        inner = shard(cfg.ssm_heads, tp) * cfg.ssm_head_dim
        n_state = cfg.ssm_state
        # x/z/B/C/dt streams + conv + gated norm + out
        per_layer = 4 * d + 4 * inner + 4 * n_state + 2 * inner + 2 * d
        # chunked SSD: states (H,N,P) per chunk per device
        per_layer += 2 * inner * (n_state / cfg.ssm_chunk)
        passes = l * per_layer
        if cfg.family == "hybrid":
            n_attn = sum(1 for k in cfg.layer_kinds() if k == "mamba_attn")
            hd = cfg.resolved_head_dim
            qkv = shard(cfg.num_heads, tp) * hd + 2 * shard(cfg.num_kv_heads,
                                                            tp) * hd
            n_kv_blocks = max(s // 1024, 1)
            passes += n_attn * (4 * d + 2 * qkv +
                                2 * shard(cfg.num_kv_heads, tp) * hd * n_kv_blocks
                                + 4 * shard(cfg.d_ff, tp) + 2 * d)

    # logits: write + read fp32 over sharded vocab
    v_loc = shard(cfg.padded_vocab, tp)
    logits = 2 * v_loc * 4 / bt             # in units of bf16-elements
    fwd = (passes + logits) * t_loc * bt
    if not train:
        return fwd
    # backward: dgrad streams ~= forward streams; remat re-runs forward
    remat_mult = {"none": 2.0, "dots": 2.6, "full": 3.0}[cfg.remat_policy]
    return fwd * remat_mult


def analytic_hbm_traffic(cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh,
                         plan, razor=None) -> Dict[str, float]:
    """Per-device HBM bytes for one step. `plan` is a StatePlan."""
    p_loc = sharded_bytes(plan.state_specs["params"], plan.param_pspecs, mesh)
    o_loc = sharded_bytes(plan.state_specs["opt"],
                          {"master": plan.opt_pspecs["master"],
                           "m": plan.opt_pspecs["m"],
                           "v": plan.opt_pspecs["v"]}, mesh)
    out: Dict[str, float] = {"params_local": float(p_loc),
                             "opt_local": float(o_loc)}
    if shape.kind == "train":
        # weights: fwd + bwd + remat re-read; grads write+read (bf16);
        # opt read+write; params re-write; backup shard read+write
        w_reads = 3 if cfg.remat_policy != "none" else 2
        traffic = (w_reads + 1 + 2) * p_loc + 2 * o_loc
        if razor is not None:
            traffic += 2 * razor.unique_bytes / max(mesh.size, 1)
        traffic += _activation_traffic(cfg, shape, mesh, train=True)
        out["traffic"] = float(traffic)
    elif shape.kind == "prefill":
        from repro.models import build_model
        from repro.parallel import sharding as shd
        model = build_model(cfg)
        cache_specs = model.cache_specs(shape.global_batch, shape.seq_len)
        cache_ps = shd.cache_pspecs(cfg, cache_specs, mesh)
        c_loc = sharded_bytes(cache_specs, cache_ps, mesh)
        out["cache_local"] = float(c_loc)
        traffic = p_loc + c_loc \
            + _activation_traffic(cfg, shape, mesh, train=False)
        out["traffic"] = float(traffic)
    else:  # decode: params + full cache read per token
        from repro.models import build_model
        from repro.parallel import sharding as shd
        model = build_model(cfg)
        cache_specs = model.cache_specs(shape.global_batch, shape.seq_len)
        cache_ps = shd.cache_pspecs(cfg, cache_specs, mesh)
        c_loc = sharded_bytes(cache_specs, cache_ps, mesh)
        # MoE: only routed experts are touched per decode step
        p_eff = p_loc
        if cfg.is_moe:
            e = cfg.padded_experts
            touched = min(e, shape.global_batch * cfg.top_k)
            expert_frac = touched / e
            from repro.models import param_count
            # expert params dominate; scale total conservatively
            p_eff = p_loc * (0.3 + 0.7 * expert_frac)
        out["cache_local"] = float(c_loc)
        out["traffic"] = float(p_eff + c_loc)
    return out
