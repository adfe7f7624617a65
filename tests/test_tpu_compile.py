"""Real-width compiles for a described TPU v5e (no chip needed).

The TPU compiler is installed with jaxlib's TPU plugin: it compiles for a
described `v5e:2x2` topology and refuses what the chip would refuse — a
block not aligned to the (8, 128) tile, more VMEM than a kernel may scope,
a program that does not fit HBM. Interpret-mode tests (test_kernels.py)
cannot see any of that. Nothing here runs; these are compiles only.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file. Keep every such test in this one file.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ShapeConfig, get_arch
from repro.kernels import decode_attn, flash_attention, ssd
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.optim import AdamWConfig
from repro.parallel.sharding import to_named
from repro.runtime.cluster import loop_step
from repro.train.state import make_state_specs
from repro.train.step import build_train_step

V5E_HBM = 15.75 * 2 ** 30       # what the v5e compiler lets a program use
HP = AdamWConfig(warmup_steps=5, total_steps=10)


@pytest.fixture(scope="module")
def no_compile_cache():
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _peak(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _qwen(num_layers: int):
    cfg = get_arch("qwen3-0.6b")
    return dataclasses.replace(cfg, num_layers=num_layers,
                               remat_policy="none")


def test_flash_attention_compiles_at_qwen3_width(one_chip):
    cfg = _qwen(1)
    qkv = _on(one_chip, (1, 4096, cfg.num_heads, cfg.resolved_head_dim))
    c = jax.jit(lambda q, k, v: flash_attention.flash_attention(
        q, k, v, True, 128, 256, False)).lower(qkv, qkv, qkv).compile()
    assert "tpu_custom_call" in c.as_text()


def test_decode_attention_compiles_with_32k_cache(one_chip):
    cfg = _qwen(1)
    hd = cfg.resolved_head_dim
    q = _on(one_chip, (1, 1, cfg.num_heads, hd))
    kv = _on(one_chip, (1, 32768, cfg.num_kv_heads, hd))
    c = jax.jit(lambda q, k, v, n: decode_attn.decode_attention(
        q, k, v, n, interpret=False)).lower(
            q, kv, kv, _on(one_chip, (), jnp.int32)).compile()
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes == 0   # no cache copy


def test_ssd_compiles_at_mamba2_width(one_chip):
    cfg = get_arch("mamba2-2.7b")
    s, h, p, n = 2048, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    c = jax.jit(lambda x, dt, a, b, cm: ssd.ssd(
        x, dt, a, b, cm, chunk=cfg.ssm_chunk, interpret=False)).lower(
            _on(one_chip, (1, s, h, p)),
            _on(one_chip, (1, s, h), jnp.float32),
            _on(one_chip, (h,), jnp.float32),
            _on(one_chip, (1, s, n)), _on(one_chip, (1, s, n))).compile()
    assert "tpu_custom_call" in c.as_text()


def test_loop_step_fits_one_chip(one_chip):
    """The failover loop's step at full qwen3-0.6b width (depth cut to 2),
    at the batch chip_smoke.py trains: the donated state is aliased, and
    the program fits the chip's HBM."""
    model = build_model(_qwen(2))
    state = jax.tree.map(lambda s: _on(one_chip, s.shape, s.dtype),
                         make_state_specs(model))
    batch = {"tokens": _on(one_chip, (8, 129), jnp.int32)}
    c = loop_step(model, HP).lower(state, batch).compile()
    m = c.memory_analysis()
    assert m.alias_size_in_bytes >= 0.99 * m.argument_size_in_bytes
    assert _peak(c) < V5E_HBM


def test_expert_share_step_fits_one_chip(one_chip):
    """The loop's step of the benchmark's `deepseek-v2-lite-e8` (the dense
    layer and 5 MoE layers at published widths, 8 of 64 experts held, 2 x
    4,096 tokens, full remat) fits the chip's HBM beside its donated state,
    and the held experts' matmuls are the chip's own ragged dots."""
    import json
    from pathlib import Path
    from repro.configs import ArchConfig
    conf = json.loads((Path(__file__).resolve().parents[1] / "bench"
                       / "configs" / "deepseek-v2-lite-e8.json").read_text())
    model = build_model(ArchConfig(**conf["arch"]))
    state = jax.tree.map(lambda s: _on(one_chip, s.shape, s.dtype),
                         make_state_specs(model))
    tr = conf["train"]
    batch = {"tokens": _on(one_chip, (tr["global_batch"], tr["seq_len"] + 1),
                           jnp.int32)}
    c = loop_step(model, HP).lower(state, batch).compile()
    m = c.memory_analysis()
    assert m.alias_size_in_bytes >= 0.99 * m.argument_size_in_bytes
    assert _peak(c) < V5E_HBM
    assert "ragged-dot" in c.as_text()


@pytest.mark.parametrize("mesh_shape", [(4, 1), (2, 2)])
def test_expert_layer_keeps_rows_on_their_data_shard(topo, mesh_shape):
    """`build_train_step` of a small MoE model on a data x model mesh of
    described chips: each shard's grouped matmul is the chip's ragged dot
    over its own rows with the experts it holds, and the expert layer
    gathers nothing across chips: its collectives are sums (the balance
    loss's over the batch, the model shards' parts and their gradients)."""
    import re
    from repro.configs import reduce_for_smoke
    model = build_model(reduce_for_smoke(get_arch("qwen3-moe-30b-a3b")))
    mesh = make_mesh(mesh_shape, ("data", "model"), devices=topo.devices[:4])
    shape = ShapeConfig("t", 128, 8, "train")
    art = build_train_step(model, mesh, HP, shape=shape)
    on = lambda specs, pspecs: jax.tree.map(
        lambda s, sh: _on(sh, s.shape, s.dtype), specs,
        to_named(pspecs, mesh))
    with mesh:
        c = art.step_fn.lower(
            on(make_state_specs(model), art.plan.state_pspecs),
            on(model.input_specs(shape), art.input_pspecs)).compile()
    text = c.as_text()
    assert "ragged-dot" in text
    layer = [line for line in text.splitlines()
             if re.search(r"(all-gather|all-to-all|all-reduce)(-start)?\(",
                          line)
             and re.search(r"closed_call/(moe\.|shard_map/)", line)]
    assert layer and all(" all-reduce" in line for line in layer), layer


def test_sharded_step_permutes_backup_across_chips(topo):
    """`build_train_step` on a (4, 1) data mesh of described chips: the
    instant checkpoint is a collective-permute in the compiled program."""
    model = build_model(_qwen(2))
    mesh = make_mesh((4, 1), ("data", "model"), devices=topo.devices[:4])
    shape = ShapeConfig("t", 128, 8, "train")
    art = build_train_step(model, mesh, HP, shape=shape)
    on = lambda specs, pspecs: jax.tree.map(
        lambda s, sh: _on(sh, s.shape, s.dtype), specs,
        to_named(pspecs, mesh))
    with mesh:
        c = art.step_fn.lower(
            on(make_state_specs(model), art.plan.state_pspecs),
            on(model.input_specs(shape), art.input_pspecs)).compile()
    assert "collective-permute" in c.as_text()
    assert _peak(c) < V5E_HBM
