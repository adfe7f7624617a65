"""The loop's one-device step and the mesh step run the same update body:
from one state and batch they reach the same states and losses."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ShapeConfig, get_arch, reduce_for_smoke
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.optim import AdamWConfig
from repro.train.state import init_state
from repro.train.step import build_train_step, loop_step

HP = AdamWConfig(warmup_steps=0, total_steps=100)


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "deepseek-v2-lite"])
def test_loop_step_equals_the_mesh_step(arch):
    cfg = dataclasses.replace(reduce_for_smoke(get_arch(arch)),
                              dtype="float32")
    model = build_model(cfg)
    rows, seq = 2, 16
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (rows, seq + 1)),
        jnp.int32)}

    loop_state = init_state(model, jax.random.key(0))
    step = loop_step(model, HP)
    loop_losses = []
    for _ in range(2):
        loop_state, loss = step(loop_state, batch)
        loop_losses.append(float(loss))

    mesh = make_mesh((1, 1), ("data", "model"))
    art = build_train_step(model, mesh, HP, instant_ckpt=False, donate=False,
                           shape=ShapeConfig("t", seq, rows, "train"))
    mesh_state = init_state(model, jax.random.key(0))
    mesh_losses = []
    with mesh:
        for _ in range(2):
            mesh_state, metrics, backup = art.step_fn(mesh_state, batch)
            mesh_losses.append(float(metrics["loss"]))

    assert all(b is None for b in jax.tree.leaves(
        backup, is_leaf=lambda x: x is None))
    np.testing.assert_allclose(mesh_losses, loop_losses, rtol=1e-6)
    assert int(mesh_state["step"]) == int(loop_state["step"]) == 2
    for path, a in jax.tree_util.tree_leaves_with_path(loop_state):
        b = dict(jax.tree_util.tree_leaves_with_path(mesh_state))[path]
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))
