"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic and metrics are found by name from
`BENCHMARK.json` at the root of the checkout; each lives in a file of its
own under `bench/` (`configs/<config>.json` beside its reference
`configs/<config>.py`, `traffic/<mix>.json`, `metrics/<metric>.py`). The
last line printed is one JSON object; with `--trace 0` its metrics are the
cell's end-to-end metrics, with `--trace 1` its per-layer metrics. Without
a TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"

# each number a run is compared on that has an exact answer: limit 0
EXACT = ("held_copy_mismatch", "restored_state_mismatch", "rows_repeated")


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    if spec is None or not path.exists():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(spec: Dict[str, Any], name: str):
    """The cell `name` of `spec` (BENCHMARK.json): its configuration file
    and reference module, its traffic file, and the metrics it reports."""
    from bench.drive import Cell
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = json.loads((ROOT / conf["file"]).read_text())
    ref = load_module((ROOT / conf["file"]).with_suffix(".py"))
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    return Cell(name, config, ref, traffic), w


def metrics_for(spec, cell: str, traced: bool) -> List[Dict[str, Any]]:
    group = spec["per_layer"] if traced else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def read_metrics(entries, record) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in entries:
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def limits_for(config: Dict[str, Any], numbers) -> Dict[str, float]:
    """The limit of each number the run compared."""
    return {k: 0 if k in EXACT else config["limits"][k] for k in numbers}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, entry = load_cell(spec, args.workload)

    import jax
    from bench import drive, peaks, probes
    devs = probes.tpu_devices(entry["chips"])
    kind = devs[0].device_kind
    peak = peaks.peaks(kind)["bf16_flops"]
    # the program's own cache directory (`$JAX_COMPILATION_CACHE_DIR`, else
    # `.jax_cache` in the checkout), holding every program however quick
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    out = drive.run(cell, args.seed, args.seconds, bool(args.trace),
                    T_START, peak)
    rec, numbers = out["record"], out["numbers"]
    limits = limits_for(cell.config, numbers)
    correct = all(numbers[k] <= limits[k] for k in limits)
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result: Dict[str, Any] = {
        "correct": correct,
        "attempted": rec.steps + 3 + len(rec.kills),
        "failed": 0 if correct else 1,
        "metrics": read_metrics(metrics_for(spec, cell.name, bool(args.trace)),
                                rec),
        "device": device,
    }
    if args.trace:
        from bench import trace
        if rec.trace is not None:
            device.update(busy_s=rec.trace.busy_s,
                          window_s=rec.trace.window_s)
            result["breakdown"] = trace.breakdown(rec.trace)
    # a number that is not finite (a NaN loss) is printed as its name, so
    # that the line stays strict JSON
    result["compared"] = {
        k: {"value": numbers[k] if math.isfinite(numbers[k])
            else repr(numbers[k]), "limit": limits[k]} for k in limits}
    for k in limits:
        print(f"{k}: {numbers[k]!r} (limit {limits[k]!r})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
