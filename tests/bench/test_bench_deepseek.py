"""DeepSeek-V2-Lite at one chip's share (`bench/configs/deepseek-v2-lite-e8`)
against its plain float32 reference, at smoke size on the CPU: 1 dense and
2 MoE layers of width 64, latent attention with q.k and v head sizes that
differ, 8 experts of which 4 are held, top-3."""
import dataclasses
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_small import ROOT

CONFIG = "deepseek-v2-lite-e8"
SMALL = dict(num_layers=3, first_k_dense=1, d_model=64, num_heads=4,
             num_kv_heads=4, d_ff=128, vocab_size=300, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             num_experts=8, experts_held=4, top_k=3, moe_d_ff=32,
             shared_expert_d_ff=64)

# Limits of the compared numbers at this size, from CPU readings at this
# size (the program on seeds 1-8 under nockpt and steady, 1-3 under
# failover; the control, the half-batch fault and the resumed step left
# unchanged on seeds 1-3): the program at most loss 1.3e-4, grad_gap 0.010,
# grad_dev 0.075, change_gap 2.6e-3, change_gap.4 2.9e-3; the control at
# least loss 1.0e-3, grad_gap 0.042, grad_dev 0.49, change_gap 0.011,
# change_gap.4 0.012; half the batch at least grad_dev 0.84; the resumed
# step unchanged change_gap.4 0.21. The worst gradient leaves are the dense
# layer's, bfloat16 rounding at width 64. The configuration's own file
# holds its limits at the size the chip runs.
LIMITS = {"loss_gap": 4e-4, "grad_gap": 0.02, "grad_dev": 0.2,
          "change_gap": 0.006, "change_gap.4": 0.006}


@functools.lru_cache(maxsize=None)
def _reference():
    """The reference module, loaded once, so that the reference's compiled
    programs (cached by module) serve every run of this file."""
    from bench.run import load_module
    return load_module(ROOT / "bench" / "configs" / f"{CONFIG}.py")


def small_cell(traffic: str = "nockpt", **arch):
    from bench.drive import Cell
    path = ROOT / "bench" / "configs" / f"{CONFIG}.json"
    cell = Cell(f"{CONFIG}.{traffic}", json.loads(path.read_text()),
                _reference(),
                json.loads((ROOT / "bench" / "traffic" / f"{traffic}.json")
                           .read_text()))
    cell.config["arch"].update(SMALL, remat_policy="none", **arch)
    cell.config["train"].update(global_batch=4, seq_len=16)
    cell.config["reference_rows"] = 2
    cell.config["limits"] = dict(LIMITS)
    return cell


def judged(traffic: str, seed: int = 11):
    from bench import drive
    from bench.run import limits_for
    cell = small_cell(traffic)
    out = drive.run(cell, seed, 0.3, False, time.perf_counter(), 1e12)
    numbers = out["numbers"]
    limits = limits_for(cell.config, numbers)
    return all(numbers[k] <= limits[k] for k in limits), numbers


def _cfg(**arch):
    from repro.configs import ArchConfig
    return ArchConfig(**{**small_cell(**arch).config["arch"],
                         "dtype": "float32"})


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


# --------------------------------------------------------------------------- #
def test_reference_agrees_with_the_model():
    """The same loss and gradients from the same weights, the program run
    in float32 too."""
    from repro.models import build_model
    from bench.reference import exact_mm
    cell = small_cell(dtype="float32")
    arch = cell.config["arch"]
    model = build_model(_cfg())
    params = _f32(cell.ref.init_params(arch, jax.random.key(3)))
    tokens = jax.random.randint(jax.random.key(4), (4, 17), 0,
                                arch["vocab_size"])
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.jit(jax.value_and_grad(
            lambda p: model.loss(p, {"tokens": tokens})[0]))(params)
        lr, gr = jax.jit(jax.value_and_grad(
            lambda p: cell.ref.loss(p, tokens, arch, exact_mm)))(params)
    assert abs(float(lp) - float(lr)) < 1e-5 * abs(float(lr))
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.linalg.norm(a - b) <= 1e-4 * np.linalg.norm(b) + 1e-9


def _layer(seed: int, cfg):
    """One MoE layer's weights for every expert (the reference's cell holds
    them all), and an input (B, S, D)."""
    cell = small_cell(experts_held=cfg.num_experts)
    p = jax.tree.map(lambda x: x[0], _f32(cell.ref.init_params(
        cell.config["arch"], jax.random.key(seed))["blocks"]["moe"]))
    x = jax.random.normal(jax.random.key(seed + 1), (2, 16, cfg.d_model))
    return p, x, cell


def _share(p, cfg):
    lo, n = cfg.expert_offset, cfg.held_experts
    return {**p, **{k: p[k][lo:lo + n] for k in ("w_gate", "w_up",
                                                 "w_down")}}


def _reference_layer(cell, p, x):
    from bench.reference import exact_mm
    routed, _ = cell.ref._experts(x, p, cell.config["arch"], exact_mm)
    return routed + cell.ref._swiglu(x, p["shared"], exact_mm)


def test_shares_add_up_to_the_uncut_layer():
    """The parts of the two shares of 4 experts, with the shared experts
    counted once, add up to the uncut reference layer."""
    from repro.models import moe
    from bench.reference import exact_mm
    shares = [_cfg(expert_offset=off) for off in (0, 4)]
    p, x, cell = _layer(7, shares[0])
    with jax.default_matmul_precision("highest"):
        parts = [moe.moe_apply(_share(p, c), c, x)[0] for c in shares]
        shared = cell.ref._swiglu(x, p["shared"], exact_mm)
        whole = _reference_layer(cell, p, x)
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1] - shared),
                               np.asarray(whole), rtol=2e-5, atol=2e-5)
    # neither share alone is the whole layer
    for part in parts:
        assert float(jnp.max(jnp.abs(part - whole))) > 1e-2


def test_no_token_is_dropped_when_every_token_picks_one_held_expert():
    """A router that sends every token to expert 1 (held): each of the 32
    tokens' rows is computed, as in the reference."""
    from repro.models import moe
    cfg = _cfg()
    p, x, cell = _layer(9, cfg)
    x = x.at[..., 0].set(10.0)
    p["router"] = p["router"].at[:, 1].set(0.0).at[0, 1].set(10.0)
    with jax.default_matmul_precision("highest"):
        top_w, top_e, _ = moe.route(p, cfg, x)
        out = moe.moe_apply(_share(p, cfg), cfg, x)[0]
        ref = _reference_layer(cell, p, x)
    assert bool(jnp.all(jnp.any(top_e == 1, axis=-1)))
    assert float(jnp.min(jnp.max(top_w, axis=-1))) > 0.99
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_latent_attention_heads_match_dense_attention():
    """Blockwise attention with a q.k head size (24) other than v's (16) and
    an explicit scale, over several key blocks, equals the dense form."""
    from repro.models.attention import blockwise_attention, dense_attention
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (2, 40, 4, 24))
    k = jax.random.normal(ks[1], (2, 40, 4, 24))
    v = jax.random.normal(ks[2], (2, 40, 4, 16))
    with jax.default_matmul_precision("highest"):
        got = blockwise_attention(q, k, v, causal=True, kv_block=16,
                                  scale=0.37)
        want = dense_attention(q, k, v, causal=True, scale=0.37)
    assert got.shape == (2, 40, 4, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("traffic", ["nockpt", "steady", "failover"])
def test_a_sound_run_is_correct(traffic):
    """The held copies and the restore take this state tree (an unstacked
    dense layer, expert stacks, an untied head) bitwise."""
    ok, numbers = judged(traffic)
    assert ok, numbers
    for k in ("held_copy_mismatch", "restored_state_mismatch"):
        assert numbers.get(k, 0) == 0, numbers


def test_the_control_fails():
    from bench import control
    cell = small_cell()
    for seed in (1, 2, 3):
        gaps = control.readings(cell.config, cell.ref, seed)["control"]
        assert any(gaps[k] > LIMITS[k] for k in LIMITS), gaps


def test_a_step_over_half_the_batch_fails(monkeypatch):
    from repro.runtime import cluster
    build = cluster.build_model

    def half(cfg):
        m = build(cfg)
        return dataclasses.replace(m, loss=lambda p, b: m.loss(
            p, {"tokens": b["tokens"][:b["tokens"].shape[0] // 2]}))
    monkeypatch.setattr(cluster, "build_model", half)
    ok, numbers = judged("nockpt")
    assert not ok, numbers


def test_flops_by_hand():
    cell = small_cell()
    # latent attention a layer: q 64*4*24 = 6144, kv_a 64*40 = 2560,
    # kv_b 32*4*32 = 4096, o 64*64 = 4096: 16896; dense SwiGLU 3*64*128 =
    # 24576; MoE: router 64*8 = 512, routed 3 of 8 choices with 4 held,
    # 1.5 experts of 3*64*32 = 9216, shared 3*64*64 = 12288: 22016.
    # 3 * 16896 + 24576 + 2 * 22016 + head 300*64 = 138496 weights, 6 FLOPs
    # each = 830976; attention 3 layers * 2 * 4 heads * (24 + 16) * (16+1)/2
    # = 8160 forward, 24480 with the backward
    assert cell.ref.flops_per_token(cell.config["arch"], 16) \
        == 830976 + 24480


def test_weights_counted_are_the_programs_matmul_weights():
    """`flops_per_token` is 6 x the program's matrices (the routed experts
    at their expected share of top_k / num_experts, the head over the
    slice; no norms, no embedding) plus causal attention x 3."""
    from repro.models import build_model
    cell = small_cell()
    arch, cfg = cell.config["arch"], _cfg()
    shapes = build_model(cfg).param_specs()
    n_moe = cfg.num_layers - cfg.first_k_dense
    dense = [x.shape for x in jax.tree.leaves(shapes["dense"])]
    moe = [x.shape[1:] for x in jax.tree.leaves(shapes["blocks"])]
    size = lambda shapes, ndim: sum(int(np.prod(s)) for s in shapes
                                    if len(s) == ndim)
    mats = size(dense, 2) + n_moe * size(moe, 2)
    routed = n_moe * size(moe, 3)
    weights = mats + routed * cfg.top_k / cfg.num_experts \
        + arch["vocab_size"] * cfg.d_model
    attn = cfg.num_layers * 2 * cfg.num_heads * (
        cfg.qk_nope_head_dim + cfg.qk_rope_head_dim + cfg.v_head_dim) * 17 / 2
    assert cell.ref.flops_per_token(arch, 16) == pytest.approx(
        6 * weights + 3 * attn)


@functools.lru_cache(maxsize=None)
def _op_names():
    """The `op_name` of every op of the compiled step, the names a device
    trace reads."""
    import re
    from repro.models import build_model
    from repro.optim import AdamWConfig
    from repro.runtime.cluster import loop_step
    from repro.train.state import init_state
    model = build_model(_cfg())
    state = init_state(model, jax.random.key(0))
    batch = {"tokens": jnp.zeros((2, 17), jnp.int32)}
    text = loop_step(model, AdamWConfig()).lower(state, batch).compile() \
        .as_text()
    return frozenset(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("scope", ["mla", "moe.route", "moe.experts",
                                   "moe.shared"])
def test_the_compiled_step_names_its_scopes(scope):
    """Ops of the forward and of the backward carry each new scope (inside
    the scanned layers too, under `while/body`)."""
    for part in ("jvp(forward)", "transpose(jvp(forward))"):
        assert any(n.startswith(f"jit(step)/{part}/") and f"/{scope}/" in n
                   for n in _op_names()), part
