"""Architecture & shape registry.

Every assigned architecture (plus the paper's own four workloads) is a frozen
``ArchConfig``. Shapes are the assignment's four (seq_len, global_batch) cells.
Configs are pure data — model code lives in ``repro.models``.
"""
from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclass(frozen=True)
class ArchConfig:
    """One architecture. All sizes are the *full* production config."""

    name: str
    family: str  # dense | moe | ssm | hybrid | encdec | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // num_heads
    # --- MLP / norm flavor ---
    mlp_type: str = "swiglu"  # swiglu | geglu | sq_relu | gelu
    use_qk_norm: bool = False
    tie_embeddings: bool = False
    rope_theta: float = 10_000.0
    # --- YaRN rotary positions (DeepSeek-V2); yarn_factor 0 -> plain rope ---
    yarn_factor: float = 0.0
    yarn_original_max_position: int = 0
    yarn_mscale_all_dim: float = 0.0  # softmax scale x mscale^2
    # --- multi-head latent attention (DeepSeek-V2); kv_lora_rank 0 -> off ---
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # --- MoE ---
    num_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    num_shared_experts: int = 0
    shared_expert_d_ff: int = 0
    shared_expert_gate: bool = True   # Qwen2-MoE's sigmoid gate
    norm_topk_prob: bool = True
    # this chip's share of an expert-parallel layer: experts
    # [expert_offset, expert_offset + experts_held); 0 -> every expert
    experts_held: int = 0
    expert_offset: int = 0
    seq_aux: bool = False             # DeepSeek's sequence-wise balance loss
    aux_loss_alpha: float = 0.01
    first_k_dense: int = 0            # leading dense blocks before the MoE
    # --- SSM (Mamba2 / SSD) ---
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv_kernel: int = 4
    ssm_chunk: int = 256
    # --- hybrid (Zamba2-style): one shared attention block every k layers ---
    attn_every: int = 0
    # --- encoder-decoder (Whisper) ---
    encoder_layers: int = 0
    encoder_seq: int = 0  # precomputed frame embeddings (conv frontend stubbed)
    # --- VLM (InternVL2): precomputed patch embeddings (ViT frontend stubbed) ---
    num_patch_tokens: int = 0
    # --- numerics / memory ---
    dtype: str = "bfloat16"
    remat_policy: str = "full"  # none | full | dots
    # --- capability flags ---
    sub_quadratic: bool = False  # can run long_500k
    source: str = ""

    # ------------------------------------------------------------------ #
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.num_heads if self.num_heads else 0

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a multiple of 256 so TP-16 shards evenly (Megatron-style)."""
        return _round_up(self.vocab_size, 256)

    @property
    def padded_experts(self) -> int:
        """Experts padded to a multiple of 16 so EP-16 shards an uncut
        layer's expert stack evenly; the router never picks a pad."""
        return _round_up(self.num_experts, 16) if self.num_experts else 0

    @property
    def held_experts(self) -> int:
        """Experts whose weights this layer holds."""
        return self.experts_held or self.padded_experts

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def ssm_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.ssm_inner // self.ssm_head_dim if self.ssm_state else 0

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    def layer_kinds(self) -> Tuple[str, ...]:
        """Per-layer block kind for the decoder stack."""
        if self.family == "ssm":
            return ("mamba",) * self.num_layers
        if self.family == "hybrid":
            k = self.attn_every
            return tuple(
                "mamba_attn" if (i % k == k - 1) else "mamba"
                for i in range(self.num_layers)
            )
        return ("attn",) * self.num_layers


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode
    requires_sub_quadratic: bool = False


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode", requires_sub_quadratic=True),
}

_REGISTRY: Dict[str, ArchConfig] = {}

# The ten assigned architectures (dry-run + roofline targets).
ASSIGNED: Tuple[str, ...] = (
    "deepseek-67b",
    "qwen3-0.6b",
    "nemotron-4-15b",
    "gemma-2b",
    "whisper-small",
    "mamba2-2.7b",
    "zamba2-7b",
    "qwen3-moe-30b-a3b",
    "qwen2-moe-a2.7b",
    "internvl2-26b",
)

# The paper's own evaluation workloads (Table 4).
PAPER_WORKLOADS: Tuple[str, ...] = (
    "gpt2-2.7b",
    "llama3-8b",
    "llama2-13b",
    "llama3-70b",
)

_MODULES = (
    "deepseek_67b",
    "qwen3_0_6b",
    "nemotron_4_15b",
    "gemma_2b",
    "whisper_small",
    "mamba2_2_7b",
    "zamba2_7b",
    "qwen3_moe_30b_a3b",
    "qwen2_moe_a2_7b",
    "internvl2_26b",
    "paper_workloads",
    "deepseek_v2_lite",
)


def register(cfg: ArchConfig) -> ArchConfig:
    if cfg.name in _REGISTRY:
        raise ValueError(f"duplicate arch {cfg.name}")
    _REGISTRY[cfg.name] = cfg
    return cfg


def _load_all() -> None:
    for mod in _MODULES:
        importlib.import_module(f"repro.configs.{mod}")


def get_arch(name: str) -> ArchConfig:
    if not _REGISTRY:
        _load_all()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}") from None


def list_archs() -> Tuple[str, ...]:
    if not _REGISTRY:
        _load_all()
    return tuple(sorted(_REGISTRY))


def get_shape(name: str) -> ShapeConfig:
    try:
        return SHAPES[name]
    except KeyError:
        raise KeyError(f"unknown shape {name!r}; known: {sorted(SHAPES)}") from None


def dryrun_cells(include_skips: bool = False):
    """All (arch, shape) dry-run cells; skips long_500k for full-attention archs."""
    cells = []
    for arch_name in ASSIGNED:
        cfg = get_arch(arch_name)
        for shape in SHAPES.values():
            skip = shape.requires_sub_quadratic and not cfg.sub_quadratic
            if skip and not include_skips:
                continue
            cells.append((cfg, shape, skip))
    return cells


def reduce_for_smoke(cfg: ArchConfig, *, seq_hint: int = 32) -> ArchConfig:
    """Shrink a production config to a CPU-smoke-testable size, preserving family
    structure (MoE stays MoE with >=8 experts, hybrid keeps its attention cadence,
    enc-dec keeps both stacks)."""
    changes = dict(
        name=cfg.name + "-smoke",
        num_layers=min(cfg.num_layers, 4 if cfg.family in ("hybrid",) else 2),
        d_model=64,
        num_heads=4,
        num_kv_heads=min(cfg.num_kv_heads, 2) if cfg.num_kv_heads < cfg.num_heads else 4,
        head_dim=16,
        d_ff=128 if cfg.d_ff else 0,
        vocab_size=256,
        remat_policy="none",
    )
    if cfg.num_kv_heads == 1:
        changes["num_kv_heads"] = 1
    if cfg.is_moe:
        changes.update(num_experts=8, top_k=min(cfg.top_k, 2), moe_d_ff=32)
        if cfg.num_shared_experts:
            changes.update(num_shared_experts=2, shared_expert_d_ff=32)
    if cfg.ssm_state:
        changes.update(ssm_state=16, ssm_head_dim=16, ssm_chunk=8)
    if cfg.family == "hybrid":
        changes.update(attn_every=2)
    if cfg.encoder_layers:
        changes.update(encoder_layers=2, encoder_seq=max(8, seq_hint // 2))
    if cfg.num_patch_tokens:
        changes.update(num_patch_tokens=8)
    return dataclasses.replace(cfg, **changes)
