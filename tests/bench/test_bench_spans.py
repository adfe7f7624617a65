"""The per-layer metrics that read the program's own spans: on the small
steady, failover and nockpt cells each gives a positive value in the cells
it lists and nothing in the others, and a program without the span
recorder reads as nothing."""
import sys
import time

import pytest

from bench_small import ROOT, small_cell, spec

CELLS = ("steady", "failover", "nockpt")
NAMES = ("instant_ckpt.d2h_s", "instant_ckpt.crc_s", "instant_ckpt.send_s",
         "instant_ckpt.d2h_gb", "batch.host_s", "recover.lazy_backup_s",
         "recover.stream_s", "recover.d2h_s", "recover.upload_s")
READERS = [m for m in spec()["per_layer"] if m["name"] in NAMES]


def _read(name, rec):
    from bench.run import load_module
    return load_module(ROOT / "bench" / "metrics" / f"{name}.py").read(rec)


@pytest.fixture(scope="module")
def readings():
    """Each reader on each small cell, read right after that cell's run."""
    from bench import drive
    out = {}
    for traffic in CELLS:
        cell = small_cell("qwen3-0.6b", traffic)
        rec = drive.run(cell, 23, 0.3, False, time.perf_counter(),
                        1e12)["record"]
        for m in READERS:
            out[m["name"], traffic] = _read(m["name"], rec)
    return out


def test_each_reader_is_a_metric_of_the_benchmark():
    assert [m["name"] for m in READERS] == list(NAMES)


@pytest.mark.parametrize("metric", [m["name"] for m in READERS])
@pytest.mark.parametrize("traffic", CELLS)
def test_a_reader_reads_its_cells_only(readings, metric, traffic):
    entry = next(m for m in READERS if m["name"] == metric)
    value = readings[metric, traffic]
    if f"qwen3-0.6b.{traffic}" in entry["workloads"]:
        assert value is not None and value > 0, value
    else:
        assert value is None, value


def test_the_instant_checkpoint_copies_12_bytes_a_parameter(readings):
    cell = small_cell("qwen3-0.6b", "steady")
    a = cell.config["arch"]
    import jax
    ref = cell.ref.init_params(a, jax.random.key(0))
    n = sum(x.size for x in jax.tree.leaves(ref))
    assert readings["instant_ckpt.d2h_gb", "steady"] == pytest.approx(
        12 * n / 1e9)


def test_a_program_without_the_recorder_reads_nothing(monkeypatch):
    from bench.drive import Record
    monkeypatch.setitem(sys.modules, "repro.launch.spans", None)
    rec = Record(window=(0.0, 1e12), steps=3, kills=[(0.0, 1e12)])
    steady = Record(window=(0.0, 1e12), steps=3)
    for m in READERS:
        assert _read(m["name"], rec) is None
        assert _read(m["name"], steady) is None
