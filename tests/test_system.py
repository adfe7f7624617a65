"""End-to-end system tests (deliverable c): the full stack through the public
API — examples must run, the CLI must train, benchmarks must emit CSV."""
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow    # subprocess end-to-end runs, minutes each


def _run(cmd, timeout=560):
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = "src:."
    env.setdefault("JAX_PLATFORMS", "cpu")
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=".")
    assert r.returncode == 0, f"cmd={cmd}\nstdout:\n{r.stdout[-3000:]}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_quickstart_example():
    out = _run([sys.executable, "examples/quickstart.py"])
    assert "recovered from neighbor" in out
    assert "rollback = 0 iterations" in out


def test_train_cli_with_failover():
    out = _run([sys.executable, "-m", "repro.launch.train",
                "--arch", "gemma-2b", "--steps", "8",
                "--inject-failure", "4"])
    assert "recovered from neighbor" in out
    assert "done:" in out
    # the run ends with host seconds per step by span
    assert "host seconds per step by span (8 steps):" in out
    for name in ("loop.step", "ckpt.instant", "opt.d2h", "recover.upload"):
        assert f"\n  {name} " in out, name


def test_serve_cli():
    out = _run([sys.executable, "-m", "repro.launch.serve",
                "--arch", "mamba2-2.7b", "--batch", "2",
                "--prompt-len", "8", "--gen", "6"])
    assert "decoded" in out


def test_elastic_example():
    out = _run([sys.executable, "examples/elastic_rescale.py"])
    assert "exact-cover data partition preserved" in out
