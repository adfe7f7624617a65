"""Multi-device SPMD tests (subprocess with 8 virtual host devices): the
instant-checkpoint ppermute semantics, razor classification, ZeRO sharding
and a small-mesh dry-run."""
import subprocess
import sys
import textwrap

import pytest


def _run(script: str, timeout: int = 560) -> str:
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    import os
    env["PATH"] = os.environ.get("PATH", env["PATH"])
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=".")
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_neighbor_backup_is_ring_permute():
    """After the in-step ppermute, device d holds device (d-1)'s shard."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.core.instant import neighbor_backup

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    x = jnp.arange(8, dtype=jnp.float32).reshape(4, 2)  # row r on data-rank r
    xs = jax.device_put(x, NamedSharding(mesh, P("data", "model")))

    with mesh:
        out = jax.jit(lambda t: neighbor_backup(
            {"a": t}, {"a": P("data", "model")}, mesh))(xs)
    got = np.asarray(out["a"])
    expect = np.roll(np.asarray(x), 1, axis=0)  # shard i -> rank i+1
    np.testing.assert_array_equal(got, expect)
    print("ring ok")
    """)


def test_razor_plan_on_mesh():
    """Unique = ZeRO('data')-sharded opt leaves; bytes = 12 phi/d."""
    _run("""
    import jax, numpy as np
    from repro.configs import get_arch, reduce_for_smoke
    from repro.models import build_model, param_count
    from repro.core.razor import razor_plan
    from repro.train.state import make_state_plan

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = reduce_for_smoke(get_arch("llama3-8b"))
    model = build_model(cfg)
    plan = make_state_plan(model, mesh)
    razor = razor_plan(plan.state_specs["opt"], plan.opt_pspecs,
                       plan.state_specs["params"], mesh)
    phi = param_count(cfg)
    assert razor.dp == 4
    # master+m+v fp32 = 12 bytes per param; a few tiny non-divisible leaves
    # may stay replicated (razor counts them redundant)
    assert 0.9 * 12 * phi <= razor.unique_bytes <= 12 * phi
    assert razor.reduction > 0.5
    print("razor ok", razor.unique_bytes, 12 * phi)
    """)


def test_train_step_backup_roundtrip():
    """Run a REAL sharded train step on an 8-device mesh; verify the backup
    output equals the new opt state permuted by one DP rank."""
    _run("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.configs import get_arch, reduce_for_smoke, ShapeConfig
    from repro.models import build_model
    from repro.train.state import init_state
    from repro.train.step import build_train_step

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = dataclasses.replace(reduce_for_smoke(get_arch("qwen3-0.6b")),
                              dtype="float32")
    model = build_model(cfg)
    shape = ShapeConfig("t", 16, 8, "train")
    art = build_train_step(model, mesh, shape=shape, donate=False)
    state = init_state(model, jax.random.key(0))
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 17)),
        jnp.int32)}
    with mesh:
        new_state, metrics, backup = art.step_fn(state, batch)
    assert np.isfinite(float(metrics["loss"]))

    # pick a unique leaf and check ppermute semantics on the data axis
    flat_b = jax.tree_util.tree_leaves_with_path(backup)
    flat_o = dict(jax.tree_util.tree_leaves_with_path(new_state["opt"]))
    checked = 0
    for path, bleaf in flat_b:
        if bleaf is None:
            continue
        oleaf = flat_o[tuple(path)]
        spec = None
        # find this leaf's zero axis by matching pspec from the plan
        ps = art.plan.opt_pspecs
        node = ps
        for k in path:
            node = node[k.key] if hasattr(k, "key") else node[k.idx]
        axis_pos = [i for i, part in enumerate(node)
                    if part == "data" or (isinstance(part, tuple)
                                          and "data" in part)]
        if not axis_pos:
            continue
        ax = axis_pos[0]
        o = np.asarray(oleaf, np.float32)
        b = np.asarray(bleaf, np.float32)
        shards = np.split(o, 4, axis=ax)
        rolled = np.concatenate([shards[-1]] + shards[:-1], axis=ax)
        np.testing.assert_allclose(b, rolled, rtol=1e-6, atol=1e-6)
        checked += 1
        if checked >= 5:
            break
    assert checked >= 3
    print("backup semantics ok, leaves checked:", checked)
    """)


@pytest.mark.parametrize("mesh_shape", [(4, 1), (2, 2), (1, 4)])
def test_expert_dispatch_stays_within_data_shards(mesh_shape):
    """On a data x model mesh the expert layer computes what it computes
    with no mesh, and moves no activation row, (token, choice) row or key
    between devices: each data shard dispatches its own rows, each model
    shard runs the experts it holds over them, and the model shards' parts
    are summed."""
    out = _run(f"""
    import re, jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_arch, reduce_for_smoke
    from repro.launch.mesh import make_mesh
    from repro.models.moe import moe_apply, moe_init

    cfg = reduce_for_smoke(get_arch("qwen3-moe-30b-a3b"))
    p = moe_init(jax.random.key(0), cfg, jnp.float32)
    b, s = 8, 64
    x = jax.random.normal(jax.random.key(1), (b, s, cfg.d_model))

    def loss(p, x):
        out, aux = moe_apply(p, cfg, x)
        return jnp.sum(out ** 2) + aux

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
    want = step(p, x)
    mesh = make_mesh({mesh_shape}, ("data", "model"))
    with mesh:
        xs = jax.device_put(x, NamedSharding(mesh, P("data")))
        got = step(p, xs)
        text = step.lower(p, xs).compile().as_text()
    for a, c in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        a = np.asarray(a)   # sums split over shards round differently
        np.testing.assert_allclose(np.asarray(c), a, rtol=1e-5,
                                   atol=1e-5 * np.abs(a).max())
    rows = b * s * cfg.top_k
    moved = []
    for m in re.finditer(r"\\w+\\[([0-9,]*)\\][^ ]* (all-gather|all-to-all)",
                         text):
        dims = [int(n) for n in m.group(1).split(",") if n]
        if dims[-1] == cfg.d_model or any(
                n in (rows, rows // {mesh_shape[0]}) for n in dims):
            moved.append(m.group(0))
    assert not moved, moved
    print("dispatch local ok", len(re.findall("all-reduce", text)))
    """)
    assert "dispatch local ok" in out


@pytest.mark.slow
def test_small_mesh_dryrun_all_families():
    """Lower+compile one representative per family on a 2x2x2 mesh."""
    _run("""
    import dataclasses, jax
    from repro.configs import get_arch, reduce_for_smoke, ShapeConfig
    from repro.models import build_model
    from repro.train.step import build_train_step
    from repro.train.state import make_state_specs
    from repro.train.serve import build_decode_step

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    for arch in ("deepseek-67b", "qwen3-moe-30b-a3b", "mamba2-2.7b",
                 "zamba2-7b", "whisper-small", "internvl2-26b"):
        cfg = reduce_for_smoke(get_arch(arch))
        model = build_model(cfg)
        npatch = cfg.num_patch_tokens or 0
        shape = ShapeConfig("t", 32 + npatch, 8, "train")
        art = build_train_step(model, mesh, shape=shape)
        lowered = art.step_fn.lower(make_state_specs(model),
                                    model.input_specs(shape))
        lowered.compile()
        # decode too
        dshape = ShapeConfig("d", 64, 8, "decode")
        fn, plan, _ = build_decode_step(model, mesh, dshape)
        specs = model.input_specs(dshape)
        fn.lower(plan.state_specs["params"], specs["cache"],
                 specs["token"]).compile()
        print(arch, "ok")
    """)
