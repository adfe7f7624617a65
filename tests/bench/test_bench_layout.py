"""The benchmark's files: every cell resolves by name, the peak table knows
only what it was given, and no chip means no result."""
import os
import subprocess
import sys

import pytest

from bench_small import ROOT, spec


def test_every_workload_resolves_to_its_files():
    from bench.run import load_cell, load_module, metrics_for
    s = spec()
    names = {c["name"] for c in s["configs"]}
    for w in s["workloads"]:
        assert w["config"] in names
        cell, entry = load_cell(s, w["name"])
        assert cell.traffic["drive"] in ("loop", "bare")
        for fn in ("init_params", "loss", "flops_per_token"):
            assert callable(getattr(cell.ref, fn))
        assert set(cell.config["limits"]) == {
            "loss_gap", "grad_gap", "grad_dev", "change_gap",
            "change_gap.4"}
        for traced in (False, True):
            for m in metrics_for(s, w["name"], traced):
                reader = load_module(ROOT / "bench" / "metrics"
                                     / f"{m['name']}.py")
                assert callable(reader.read)
    for m in s["per_layer"]:
        moved = next(e for e in s["end_to_end"] if e["name"] == m["moves"])
        for w in m["workloads"]:
            assert w in moved.get("workloads", [w])


def test_peak_table_refuses_an_unknown_device():
    from bench.peaks import peaks
    assert peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v9 imaginary")


def test_run_without_a_tpu_exits_nonzero_naming_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "qwen3-0.6b.steady", "--seed", "1", "--seconds", "1"],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr
    assert r.stdout.strip() == ""


def test_device_peak_counts_the_memory_reserved_for_programs():
    from bench.drive import device_peak
    stats = {"peak_bytes_in_use": 8_375_804_416,
             "peak_bytes_reserved": 5_085_085_696}
    assert device_peak(stats) == 13_460_890_112
    assert device_peak({"peak_bytes_in_use": 7}) == 7
