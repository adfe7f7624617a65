"""Each configuration's plain float32 reference computes the program's
function: the same loss and gradients from the same weights at smoke size,
with the program run in float32 too."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_small import small_cell


@pytest.mark.parametrize("config", ["qwen3-0.6b", "mamba2-2.7b-d8"])
def test_reference_agrees_with_the_model(config):
    from repro.configs import ArchConfig
    from repro.models import build_model
    from bench.reference import exact_mm
    cell = small_cell(config, dtype="float32")
    arch = cell.config["arch"]
    model = build_model(ArchConfig(**arch))
    f32 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)
    params = f32(cell.ref.init_params(arch, jax.random.key(3)))
    tokens = jax.random.randint(jax.random.key(4), (4, 17), 0,
                                arch["vocab_size"])
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(
            lambda p: model.loss(p, {"tokens": tokens})[0])(params)
        lr, gr = jax.value_and_grad(
            lambda p: cell.ref.loss(p, tokens, arch, exact_mm))(params)
    assert abs(float(lp) - float(lr)) < 1e-5 * abs(float(lr))
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.linalg.norm(a - b) <= 1e-4 * np.linalg.norm(b) + 1e-9


def test_reference_adamw_matches_the_programs_optimizer():
    """Four reference steps equal the program's loop_step in float32,
    with the change read after the third and the fourth."""
    from repro.configs import ArchConfig
    from repro.models import build_model
    from repro.optim import AdamWConfig
    from repro.runtime.cluster import loop_step
    from bench import reference
    cell = small_cell("qwen3-0.6b", dtype="float32")
    arch, hp = cell.config["arch"], cell.config["train"]["hp"]
    key = jax.random.key(5)
    batches = [np.asarray(jax.random.randint(jax.random.key(k), (4, 17), 0,
                                             arch["vocab_size"]))
               for k in range(4)]
    with jax.default_matmul_precision("highest"):
        ref = reference.train(cell.ref, arch, hp, key, batches, rows=2,
                              changes=(3, 4))
        model = build_model(ArchConfig(**arch))
        p = cell.ref.init_params(arch, key)
        f32 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)
        zeros = lambda t: jax.tree.map(jnp.zeros_like, f32(t))
        state = {"step": jnp.zeros((), jnp.int32), "params": f32(p),
                 "opt": {"master": f32(p), "m": zeros(p), "v": zeros(p)}}
        step = loop_step(model, AdamWConfig(**hp))
        losses, grads, changes = [], None, {}
        p0 = f32(p)
        for k, b in enumerate(batches):
            state, loss = step(state, {"tokens": jnp.asarray(b)})
            losses.append(float(loss))
            if k == 0:
                g = jax.tree.map(lambda m: m / (1 - hp["b1"]),
                                 state["opt"]["m"])
                grads = np.asarray(reference.leaf_norms(g))
                sk = np.asarray(reference.sketch(g))
            if k >= 2:
                changes[k + 1] = np.asarray(reference.leaf_norms(
                    jax.tree.map(jnp.subtract, state["opt"]["master"], p0)))
    prog = reference.Readings(losses, grads, sk, changes)
    gaps = reference.compare(prog, ref)
    assert gaps["loss_gap"] < 1e-6
    assert gaps["grad_gap"] < 1e-5
    assert gaps["grad_dev"] < 1e-5
    assert gaps["change_gap"] < 1e-5
    assert gaps["change_gap.4"] < 1e-5
