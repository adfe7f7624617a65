"""Small copies of the benchmark's cells, for the tests on the CPU."""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

SMALL = {
    "dense": dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                  head_dim=16, d_ff=128, vocab_size=300),
    "ssm": dict(num_layers=2, d_model=64, vocab_size=300, ssm_state=16,
                ssm_head_dim=16, ssm_chunk=8),
}


# Limits of the compared numbers at this size, from CPU readings at this
# size (the program on seeds 1-8; the control and the half-batch fault on
# seeds 1-3). Dense: the program at most loss 1.2e-4, grad_gap 1.8e-3,
# grad_dev 0.023, change_gap 2.5e-3; the control at least grad_gap 9.5e-3,
# grad_dev 0.25. The failover cell's first resumed step: the program at most
# loss 7.4e-5, change_gap.4 2.6e-3; that step returning its state unchanged
# at least change_gap.4 0.15. SSM: the program at most loss 1.1e-4,
# grad_gap 7.1e-3, grad_dev 0.032, change_gap 7.0e-3; the control at least
# loss_gap 3.0e-4, grad_dev 0.25. Each configuration's own file holds its limits
# at the size the chip runs.
SMALL_LIMITS = {
    "dense": {"loss_gap": 2e-4, "grad_gap": 0.006, "grad_dev": 0.1,
              "change_gap": 0.02, "change_gap.4": 0.02},
    "ssm": {"loss_gap": 2e-4, "grad_gap": 0.05, "grad_dev": 0.12,
            "change_gap": 0.05},
}


def spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def small_cell(config: str, traffic: str = "steady", **arch):
    """The benchmark's configuration `config` under traffic `traffic`, at
    smoke size: 2 layers of width 64, 4 rows of 16 tokens, with the limits
    of that size."""
    from bench.drive import Cell
    from bench.run import load_module
    path = ROOT / "bench" / "configs" / f"{config}.json"
    cell = Cell(f"{config}.{traffic}", json.loads(path.read_text()),
                load_module(path.with_suffix(".py")),
                json.loads((ROOT / "bench" / "traffic" / f"{traffic}.json")
                           .read_text()))
    a = cell.config["arch"]
    a.update(SMALL[a["family"]], **arch)
    cell.config["train"].update(global_batch=4, seq_len=16)
    cell.config["reference_rows"] = 2
    cell.config["limits"] = SMALL_LIMITS[a["family"]]
    return cell


def judged(config: str, traffic: str = "steady", seed: int = 11,
           seconds: float = 0.3):
    """Run the small cell (no chip check) and judge it: (correct, numbers)."""
    from bench import drive
    from bench.run import limits_for
    cell = small_cell(config, traffic)
    out = drive.run(cell, seed, seconds, False, time.perf_counter(), 1e12)
    numbers = out["numbers"]
    limits = limits_for(cell.config, numbers)
    return all(numbers[k] <= limits[k] for k in limits), numbers
