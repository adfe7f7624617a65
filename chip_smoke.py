"""On-chip smoke test of the failover training loop at published width.

    python chip_smoke.py              # one chip: train, kill a worker, recover
    python chip_smoke.py --chips 4    # four chips: the sharded step's backup

One chip (the default) drives the training CLI in-process
(`repro.launch.train.main`) on qwen3-0.6b at full width with the CLI's
default fabric: a few steps, a software failure of worker 1, recovery from
its neighbour's backup with the default stream policy, and training on. It
then frees that run, trains the same seed uninterrupted, and requires the
losses to be bitwise equal.

`--chips 4` runs only the cross-chip path: `build_train_step` on a
("data", "model") = (4, 1) mesh with the state initialised straight into
its shardings, a few steps, a bitwise check that every backup leaf is the
new optimizer shard rolled by one data rank, and the same steps on one
device with the instant checkpoint off: every loss, and the first moment
after the first update, must agree.

Both need a TPU and stop with an error naming the platform found anywhere
else. The last line printed is the JSON verdict.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import resource
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import List, Sequence, Tuple

ROOT = Path(__file__).resolve().parent
ARCH = "qwen3-0.6b"
# 8 x 128 tokens: the step compiled for a v5e peaks at 13.5 GiB with the
# donated 8.35 GB state (its memory_analysis); 8 x 256 does not fit 16 GB
BATCH, SEQ = 8, 128
STEPS, FAIL_AT = 5, 3
STEPS_4 = 3
# 4 chips against 1 device: the same math reduced in another order. The
# first moment after one step is the gradient itself: a correct reduction
# differs in bf16 rounding only, a step whose gradient comes from one
# chip's quarter of the batch differs by O(1). Adam's update is near
# sign(g), so the losses barely see that fault; their bound is a coarse
# one. Readings behind both limits: CHANGES.md
LOSS_ATOL = 1e-3
MOMENT_RTOL = 1e-1


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def tpu_devices(need: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"needs a TPU; JAX found platform {devs[0].platform!r} "
             f"({len(devs)} device(s))")
    if len(devs) < need:
        fail(f"needs {need} TPU chips; found {len(devs)}")
    return devs


class CompileLog:
    """Every executable JAX builds (compiled or read from the persistent
    cache), stamped on the `time.perf_counter` clock."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.events: List[Tuple[float, float]] = []
        event = dispatch.BACKEND_COMPILE_EVENT

        def listen(name, seconds, **_):
            if name == event:
                self.events.append((time.perf_counter(), seconds))

        jax.monitoring.register_event_duration_secs_listener(listen)

    def count(self, windows: Sequence[Tuple[float, float]]) -> int:
        return sum(1 for t, _ in self.events
                   for lo, hi in windows if lo <= t <= hi)

    def seconds(self) -> float:
        return sum(s for _, s in self.events)


class RssSampler:
    """This process's resident set size, read from /proc/self/statm every
    20 ms on a thread, so each step window reports its own peak."""

    def __init__(self, period: float = 0.02):
        self.samples: List[Tuple[float, int]] = []
        self._page = os.sysconf("SC_PAGE_SIZE")
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, args=(period,),
                                        daemon=True)
        self._thread.start()

    def _run(self, period: float) -> None:
        with open("/proc/self/statm") as f:
            while not self._stop.is_set():
                f.seek(0)
                rss = int(f.read().split()[1]) * self._page
                self.samples.append((time.perf_counter(), rss))
                self._stop.wait(period)

    def peak(self, lo: float, hi: float) -> int:
        return max((b for t, b in self.samples if lo <= t <= hi), default=0)

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()


def host_peak_rss() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def train(argv: List[str]):
    from repro.launch.train import main
    with tempfile.TemporaryDirectory() as ckpt:
        return main(argv + ["--ckpt-dir", ckpt])


def release(run) -> None:
    for w in run.cluster.workers:
        w.engine.close()


def one_chip(devs, log: CompileLog, rss: RssSampler) -> None:
    import jax
    import numpy as np
    argv = ["--arch", ARCH, "--full", "--steps", str(STEPS), "--seq-len",
            str(SEQ), "--global-batch", str(BATCH)]

    run = train(argv + ["--inject-failure", str(FAIL_AT)])
    clu = run.cluster
    n_params = sum(x.size for x in jax.tree.leaves(clu.state["params"]))
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(clu.state))
    print(f"model: {ARCH} full width, {n_params} params, "
          f"state {n_bytes} bytes on the device, "
          f"batch {BATCH}x{SEQ} tokens, {clu.dp} workers")
    walls = [hi - lo for lo, hi in run.step_windows]
    for i, ((lo, hi), loss) in enumerate(zip(run.step_windows,
                                             clu.loss_history)):
        print(f"step {i}: loss {loss!r}, wall {hi - lo:.3f} s "
              f"(train step + instant-ckpt host copy, until ready), "
              f"host RSS peak {rss.peak(lo, hi)} bytes")
    print(f"compile: first step {walls[0]:.3f} s wall; "
          f"{len(log.events)} executables built in "
          f"{log.seconds():.3f} s so far")
    if len(run.recoveries) != 1:
        fail(f"expected one recovery, saw {len(run.recoveries)}")
    rep, rec_window = run.recoveries[0]
    steady = run.step_windows[1:FAIL_AT]
    after = run.step_windows[FAIL_AT:]
    print(f"compiles: {log.count(steady)} in steps 1..{FAIL_AT - 1} "
          f"(after warm-up), {log.count([rec_window])} during recovery, "
          f"{log.count(after)} in the steps after it")
    print(f"recovery: {rep.kind} failure, from {rep.recovered_from} "
          f"({rep.policy} policy), rollback {rep.rolled_back_iterations}, "
          f"{rep.chunks_sent}/{rep.chunks_total} chunks "
          f"({rep.state_bytes_streamed:.0f} bytes) streamed; "
          f"wall {rec_window[1] - rec_window[0]:.3f} s, "
          f"host RSS peak {rss.peak(*rec_window)} bytes, "
          f"modeled fabric time {rep.total_time:.3f} s")
    superseded = sum(w.engine.superseded_chunks for w in clu.workers)
    print(f"instant checkpoint on the modeled fabric: hidden "
          f"{clu.instant_hidden}, exposed {clu.instant_exposed} of "
          f"{STEPS} steps; {superseded} stale chunks superseded")
    stats = devs[0].memory_stats() or {}
    print(f"device memory: peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 'not reported')}; host peak "
          f"RSS so far {host_peak_rss()} bytes")
    if rep.recovered_from != "neighbor" or rep.rolled_back_iterations != 0:
        fail("recovery did not restore from the neighbour with rollback 0")
    if clu.iteration != STEPS or not all(map(math.isfinite,
                                             clu.loss_history)):
        fail(f"training did not reach {STEPS} finite steps")
    losses = list(clu.loss_history)
    release(run)
    del run, clu
    gc.collect()
    print(f"device memory after freeing the run: bytes_in_use "
          f"{(devs[0].memory_stats() or {}).get('bytes_in_use')}")

    ref = train(argv)
    ref_losses = list(ref.cluster.loss_history)
    diff = [abs(a - b) for a, b in zip(losses, ref_losses)]
    print(f"reference (uninterrupted) losses: {ref_losses}")
    print(f"largest loss difference after recovery: "
          f"{max(diff[FAIL_AT:])!r} (before the failure: "
          f"{max(diff[:FAIL_AT])!r})")
    # one device, one seed, the same data order: a correct restore
    # continues bit for bit
    if ref_losses != losses:
        fail("losses disagree with the uninterrupted run")

    # the bare train step, for the share of a loop step spent on the host
    clu = ref.cluster
    batch = clu._assemble_batch()
    bare = []
    for _ in range(3):
        t0 = time.perf_counter()
        clu.state, loss = clu._step(clu.state, batch)
        jax.block_until_ready((clu.state, loss))
        bare.append(time.perf_counter() - t0)
    print(f"train step alone (until ready): {bare} s; loop step median "
          f"{float(np.median(walls[1:])):.3f} s")
    release(ref)


def four_chips(devs) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.configs import ShapeConfig, get_arch
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.optim import AdamWConfig
    from repro.parallel.sharding import to_named
    from repro.train.state import init_state
    from repro.train.step import build_train_step

    cfg = dataclasses.replace(get_arch(ARCH), remat_policy="none")
    model = build_model(cfg)
    shape = ShapeConfig("smoke", SEQ, BATCH, "train")
    # no warm-up: the first update moves the weights, so later losses
    # depend on the reduced gradient
    hp = AdamWConfig(warmup_steps=0, total_steps=10)
    rng = np.random.default_rng(0)
    tokens = [rng.integers(0, cfg.vocab_size, (BATCH, SEQ + 1), np.int32)
              for _ in range(STEPS_4)]

    def run_steps(mesh, instant_ckpt: bool, check_backup):
        """Losses, step walls, and the first moment after the first step
        (on the host)."""
        art = build_train_step(model, mesh, hp, shape=shape,
                               instant_ckpt=instant_ckpt)
        init = jax.jit(lambda k: init_state(model, k),
                       out_shardings=to_named(art.plan.state_pspecs, mesh))
        in_sh = to_named(art.input_pspecs, mesh)
        losses, walls, m1 = [], [], None
        with mesh:
            state = init(jax.random.key(0))
            for t in tokens:
                batch = jax.device_put({"tokens": jnp.asarray(t)}, in_sh)
                t0 = time.perf_counter()
                state, metrics, backup = art.step_fn(state, batch)
                jax.block_until_ready((state, metrics, backup))
                walls.append(time.perf_counter() - t0)
                losses.append(float(metrics["loss"]))
                if m1 is None:
                    m1 = [np.asarray(x) for x in
                          jax.tree.leaves(state["opt"]["m"])]
            if check_backup:
                check_backup(art, state, backup)
        return losses, walls, m1

    def check_backup(art, state, backup):
        is_p = lambda x: isinstance(x, P) or x is None
        specs, treedef = jax.tree_util.tree_flatten(art.backup_pspecs,
                                                    is_leaf=is_p)
        rolled_equal = jax.jit(
            lambda b, o, ax: jnp.all(
                jax.lax.bitcast_convert_type(b, jnp.uint32) ==
                jax.lax.bitcast_convert_type(
                    jnp.roll(o, o.shape[ax] // 4, axis=ax), jnp.uint32)),
            static_argnums=2)
        checked = 0
        for spec, b, o in zip(specs, treedef.flatten_up_to(backup),
                              treedef.flatten_up_to(state["opt"])):
            if spec is None:
                continue
            ax = next(i for i, part in enumerate(spec)
                      if part == "data" or (isinstance(part, tuple)
                                            and "data" in part))
            if not bool(rolled_equal(b, o, ax)):
                fail(f"backup leaf {spec} is not the shard rolled by one "
                     f"data rank")
            checked += 1
        if not checked:
            fail("the step returned no backup leaves")
        print(f"backup: {checked} leaves equal the new optimizer shards "
              f"rolled by one data rank, bitwise")

    mesh4 = make_mesh((4, 1), ("data", "model"), devices=devs[:4])
    losses4, walls4, m4 = run_steps(mesh4, True, check_backup)
    print(f"4 chips, instant ckpt on: losses {losses4}, step walls "
          f"{walls4} s (the first includes compile)")
    print(f"device memory, chip 0: peak_bytes_in_use "
          f"{(devs[0].memory_stats() or {}).get('peak_bytes_in_use')}")
    gc.collect()
    mesh1 = make_mesh((1, 1), ("data", "model"), devices=devs[:1])
    losses1, _, m1 = run_steps(mesh1, False, None)
    dl = [abs(a - b) for a, b in zip(losses4, losses1)]
    sq = [(float(np.vdot(a - b, a - b)), float(np.vdot(b, b)))
          for a, b in zip(m4, m1)]
    rel_m = math.sqrt(sum(d for d, _ in sq) / sum(r for _, r in sq))
    worst = max((math.sqrt(d / r), i) for i, (d, r) in enumerate(sq) if r)
    print(f"first moment, worst leaf: relative L2 difference {worst[0]!r} "
          f"(leaf {worst[1]} of {len(sq)}, shape {m1[worst[1]].shape})")
    print(f"1 device, instant ckpt off: losses {losses1}; differences "
          f"{dl} (limit {LOSS_ATOL}); first moment after step 1: relative "
          f"L2 difference {rel_m!r} (limit {MOMENT_RTOL})")
    if not all(map(math.isfinite, losses4)) or max(dl) > LOSS_ATOL:
        fail("4-chip losses disagree with one device")
    if not rel_m <= MOMENT_RTOL:
        fail("4-chip gradient (first moment) disagrees with one device")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded cross-chip path")
    args = ap.parse_args()
    devs = tpu_devices(args.chips)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.cache import use_compile_cache
    except ImportError as e:
        fail(f"cannot import the repro package next to this script: {e}")
    print(f"device: {devs[0].platform} {devs[0].device_kind} "
          f"x{len(devs)}; compile cache {use_compile_cache()}; host peak "
          f"RSS with the runtime up {host_peak_rss()} bytes")
    log = CompileLog()
    rss = RssSampler()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(devs)
        else:
            one_chip(devs, log, rss)
    finally:
        rss.stop()
    print(f"wall {time.perf_counter() - t0:.1f} s; host peak RSS "
          f"{host_peak_rss()} bytes")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
