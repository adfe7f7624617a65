"""GPipe pipeline parallelism: correctness vs unpipelined forward
(subprocess, 4 virtual devices on the pipe axis)."""
import subprocess
import sys
import textwrap


def test_pipeline_matches_sequential():
    env_script = """
    import os
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.parallel.pipeline import bubble_fraction, pipeline_forward

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("pipe",))
    L, D, M, MB, S = 8, 16, 6, 2, 4
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(L, D, D)) * 0.3, jnp.float32)}
    x = jnp.asarray(rng.normal(size=(M, MB, S, D)), jnp.float32)

    def layer(p, h):
        return jnp.tanh(h @ p["w"])

    with mesh:
        out = jax.jit(lambda p, x: pipeline_forward(layer, p, x, mesh))(
            params, x)

    # sequential reference
    ref = x
    for i in range(L):
        ref = jnp.tanh(ref @ params["w"][i])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert abs(bubble_fraction(4, 6) - 3 / 9) < 1e-9
    print("pipeline ok")
    """
    import os
    env = dict(os.environ)
    env.update({"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
                "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"})
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(env_script)],
                       capture_output=True, text=True, timeout=560, env=env,
                       cwd=".")
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "pipeline ok" in r.stdout
