"""Each configuration's FLOPs a token against a count by hand at smoke size,
and its weight count against the program's own parameter shapes."""

import jax
import numpy as np
import pytest

from bench_small import small_cell


def test_qwen3_flops_by_hand():
    cell = small_cell("qwen3-0.6b")
    # per layer: q 64*64 + k,v 2*64*32 + o 64*64 + swiglu 3*64*128 = 36864;
    # 2 layers + head 300*64 = 92928 weights, 6 FLOPs each = 557568;
    # attention 2 layers * (QK^T + PV = 2*2) * 4 heads * 16 * (16+1)/2
    # = 4352 forward, 13056 with the backward
    assert cell.ref.flops_per_token(cell.config["arch"], 16) == 557568 + 13056


def test_mamba2_flops_by_hand():
    cell = small_cell("mamba2-2.7b-d8")
    # d 64, inner 128, 8 heads of 16, state 16, conv 4, chunk 8, seq 16.
    # per layer: in 64*(2*128 + 2*16 + 8) = 18944, out 128*64 = 8192;
    # 2 layers + head 300*64 = 73472 weights, 6 FLOPs each = 440832.
    # scan, per token and layer: C B^T 2*16*4.5 = 144, per head
    # 2*16*4.5 + 2*2*16*16 = 1168, 8 heads = 9344; conv 2*4*(128+32) =
    # 1280; (144 + 9344 + 1280) * 2 layers * 3 = 64608
    assert cell.ref.flops_per_token(cell.config["arch"], 16) == 440832 + 64608


@pytest.mark.parametrize("config", ["qwen3-0.6b", "mamba2-2.7b-d8"])
def test_weights_counted_are_the_programs_matmul_weights(config):
    from repro.configs import ArchConfig
    from repro.models import build_model
    cell = small_cell(config)
    arch = cell.config["arch"]
    shapes = build_model(ArchConfig(**arch)).param_specs()
    # matrices of the stacked blocks, convolutions and the embedding left
    # out; the head counted over the published vocabulary
    mats = sum(int(np.prod(x.shape)) for p, x in
               jax.tree_util.tree_flatten_with_path(shapes["blocks"])[0]
               if x.ndim == 3 and "conv" not in jax.tree_util.keystr(p))
    weights = mats + arch["vocab_size"] * arch["d_model"]
    per_token = cell.ref.flops_per_token(arch, 1)
    assert per_token >= 6 * weights
    assert per_token < 6 * weights * 1.2
