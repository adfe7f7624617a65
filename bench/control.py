"""Upper readings of a configuration's compared numbers: the reference put
in the program's place, once as the control (float8 weight matmuls, the
precision below the configuration's bfloat16) and once with each of three
planted faults (the loss over half of each batch; every step's loss reported
1 % high, an answer altered where it is produced; the fourth step, the
failover cell's first step after a resume, returning its state unchanged),
each compared with the float32 reference on the same four batches.

    python3 bench/control.py --config qwen3-0.6b --seeds 1 2 3

A step that returns its state unchanged from the start reads 1 on
`change_gap` by the measure itself and needs no run. One JSON line per seed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
ALTERED = 0.01                    # the altered-loss fault: 1 % high
STEPS = 4                         # the failover cell compares four steps


def readings(config, ref, seed: int):
    import jax
    from bench import drive, reference
    arch, tr = config["arch"], config["train"]
    s32 = drive.seed32(seed)
    src = drive.Tokens(s32, tr["seq_len"], arch["vocab_size"])
    b = tr["global_batch"]
    batches = [src.fetch(range(k * b, (k + 1) * b)) for k in range(STEPS)]
    key = jax.random.key(s32)
    train = lambda **kw: reference.train(
        ref, arch, tr["hp"], key, batches, rows=config["reference_rows"],
        changes=(3, STEPS), **kw)
    base = train()
    altered = dataclasses.replace(
        base, losses=[x * (1 + ALTERED) for x in base.losses])
    unchanged = dataclasses.replace(
        base, changes={**base.changes, STEPS: base.changes[3]})
    return {"seed": seed,
            "control": reference.compare(train(matmul="fp8"), base),
            "half_batch": reference.compare(train(half_batch=True), base),
            "altered_loss": reference.compare(altered, base),
            "resumed_unchanged": reference.compare(unchanged, base)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT)]
    from bench import probes
    from bench.run import load_module
    probes.tpu_devices(1)
    path = ROOT / "bench" / "configs" / f"{args.config}.json"
    config = json.loads(path.read_text())
    ref = load_module(path.with_suffix(".py"))
    for seed in args.seeds:
        print(json.dumps(readings(config, ref, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
