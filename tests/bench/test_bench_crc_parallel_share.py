"""The share of the instant checkpoint's bytes whose CRCs the pool took:
none in the small steady cell's window, whose shards are under the fan-out
size, nothing read in the cells with kills or without the checkpoint, and
nothing from a program whose stream counts no parallel bytes."""
import time

import pytest

from bench_small import ROOT, small_cell, spec

NAME = "instant_ckpt.crc_parallel_share"


def _read(rec):
    from bench.run import load_module
    return load_module(ROOT / "bench" / "metrics" / f"{NAME}.py").read(rec)


def _run(traffic):
    from bench import drive
    return drive.run(small_cell("qwen3-0.6b", traffic), 29, 0.3, False,
                     time.perf_counter(), 1e12)["record"]


def test_it_is_a_metric_of_the_steady_cell():
    entry = next(m for m in spec()["per_layer"] if m["name"] == NAME)
    assert entry["workloads"] == ["qwen3-0.6b.steady"]
    assert entry["moves"] == "tokens_per_s"
    assert entry["layer"] == "instant checkpoint"


@pytest.mark.parametrize("traffic,share", [("steady", 0.0),
                                           ("failover", None),
                                           ("nockpt", None)])
def test_small_shards_stay_on_one_thread(traffic, share):
    assert _read(_run(traffic)) == share


def test_a_program_that_counts_no_parallel_bytes_reads_nothing(monkeypatch):
    import bench.program_spans as ps
    from bench.drive import Record
    from repro.launch.spans import Span
    old = [Span(1, "ckpt.instant", 1.0, 3.0, None, {}, {}),
           Span(2, "stream.chunk", 1.5, 2.0, 1, {},
                {"bytes": 12, "chunks": 1})]
    monkeypatch.setattr(ps, "_recorded", lambda: old)
    rec = Record(window=(0.0, 4.0), steps=1)
    assert _read(rec) is None
    new = old[:1] + [old[1]._replace(
        counts={"bytes": 12, "chunks": 1, "parallel_bytes": 12})]
    monkeypatch.setattr(ps, "_recorded", lambda: new)
    assert _read(rec) == 100.0
