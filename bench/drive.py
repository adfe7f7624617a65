"""The one general runner of every cell: it builds the training loop from a
configuration's file, drives it as a traffic file says, times the window,
and checks what the timed path produced.

A traffic file (`traffic/<mix>.json`) holds:
  drive   "loop": the loop's own step, `SimCluster.step`, with the instant
          checkpoint and the modeled fabric; "bare": the loop's jitted step
          alone, `loop_step`, on the same state, batches and loaders;
  kill    optional {"workers": [...]}: at each step boundary inside the
          window, kill those workers (a software failure), `recover()` them,
          and train on past the iteration reached before the kill.
"""
from __future__ import annotations

import contextlib
import gc
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import probes, reference, trace


def seed32(seed: int) -> int:
    """A 32-bit seed from any whole number, using all of its bits."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0])


class Tokens:
    """The benchmark's corpus: row i is drawn from (seed, i) alone. It
    stands in the loop's loaders for the program's own synthetic source,
    and logs every row index it hands out."""

    def __init__(self, seed: int, seq_len: int, vocab: int):
        self.seed, self.seq_len, self.vocab = seed, seq_len, vocab
        self.sample_bytes = 4 * (seq_len + 1)
        self.log: List[int] = []

    def row(self, i: int) -> np.ndarray:
        return np.random.default_rng([self.seed, int(i)]).integers(
            0, self.vocab, self.seq_len + 1, dtype=np.int32)

    def fetch(self, indices) -> np.ndarray:
        self.log.extend(int(i) for i in indices)
        return np.stack([self.row(i) for i in indices])


@dataclass
class Record:
    """What one run measured; the metric readers read it."""
    setup_s: float = 0.0
    window: Tuple[float, float] = (0.0, 0.0)
    steps: int = 0
    tokens_per_step: int = 0
    kills: List[Tuple[float, float]] = field(default_factory=list)
    spans: List[Tuple[str, float, float]] = field(default_factory=list)
    compile_log: Any = None
    trace: Optional[trace.Reduced] = None
    host_rss_bytes: int = 0
    flops_per_token: float = 0.0
    peak_flops: float = 0.0


@dataclass
class Cell:
    name: str
    config: Dict[str, Any]        # the configuration's file
    ref: Any                      # its reference module
    traffic: Dict[str, Any]


# --------------------------------------------------------------------------- #
def build(cell: Cell, seed: int, tmp: str):
    """The loop with the benchmark's weights and corpus in place of the
    program's own."""
    from repro.configs import ArchConfig
    from repro.optim import AdamWConfig
    from repro.runtime.cluster import ClusterConfig, FabricConfig, SimCluster
    arch, tr = cell.config["arch"], cell.config["train"]
    s32 = seed32(seed)
    clu = SimCluster(
        ArchConfig(**arch),
        cluster=ClusterConfig(
            dp=tr["dp"], global_batch=tr["global_batch"],
            seq_len=tr["seq_len"], hp=AdamWConfig(**tr["hp"]),
            ckpt_dir=Path(tmp), full_every=tr["full_every"],
            seed=s32 % 2 ** 31, t_iter_model=tr["t_iter_model"]),
        fabric=FabricConfig(link_bw=tr["link_bw"]),
        recovery=tr["recovery"])
    key = jax.random.key(s32)
    ours = jax.eval_shape(lambda k: cell.ref.init_params(arch, k), key)
    theirs = jax.eval_shape(lambda s: s, clu.state["params"])
    if jax.tree.structure(ours) != jax.tree.structure(theirs) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(ours), jax.tree.leaves(theirs))):
        raise RuntimeError("the program's parameter layout differs from "
                           "the reference's: " + str(jax.tree.map(
                               lambda a: a.shape, theirs)))
    for x in jax.tree.leaves(clu.state):
        x.delete()
    clu.state = _make_state(cell.ref, arch)(key)
    src = Tokens(s32, tr["seq_len"], arch["vocab_size"])
    clu.source = src
    for w in clu.workers:
        w.loader.source = src
    return clu, key, src


def _make_state(ref, arch) -> Callable:
    def make(key):
        p = ref.init_params(arch, key)
        f32 = lambda x: x.astype(jnp.float32)
        zeros = lambda x: jnp.zeros(x.shape, jnp.float32)
        return {"step": jnp.zeros((), jnp.int32), "params": p,
                "opt": {"master": jax.tree.map(f32, p),
                        "m": jax.tree.map(zeros, p),
                        "v": jax.tree.map(zeros, p)}}
    return jax.jit(make)


def stepper(clu, drive: str) -> Callable[[], float]:
    """One closed-loop step of the cell's timed path; returns its loss."""
    if drive == "loop":
        return clu.step
    from repro.runtime import cluster as rc
    fn = rc.loop_step(clu.model, clu.hp)

    def bare() -> float:
        batch = np.concatenate([w.loader.get(clu.iteration)
                                for w in clu.workers])
        clu.state, loss = fn(clu.state, {"tokens": jnp.asarray(batch)})
        clu.iteration += 1
        return float(loss)
    return bare


@jax.jit
def _fingerprint(tree):
    """One uint32 hash of each leaf's bits."""
    out = []
    for x in jax.tree.leaves(tree):
        bits = jax.lax.bitcast_convert_type(
            x, {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize])
        u = bits.reshape(-1).astype(jnp.uint32)
        pos = jnp.arange(u.size, dtype=jnp.uint32) * jnp.uint32(2654435761)
        out.append(jnp.sum((u ^ (u >> 7)) * (pos | 1), dtype=jnp.uint32))
    return jnp.stack(out)


def held_copy_mismatches(clu) -> int:
    """Elements of the neighbours' held copies that differ bitwise from the
    owner's shard of the optimizer state on the device, every element of
    every copy read back leaf by leaf; a held copy of another iteration or
    size counts whole."""
    from repro.runtime.cluster import shard_slices
    leaves = jax.tree.leaves(clu.state["opt"])
    sizes = np.array([x.size for x in leaves])
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    slices = shard_slices(int(sizes.sum()), clu.dp)
    held, bad = [], 0
    for i, sl in enumerate(slices):
        snap = clu.workers[(i + 1) % clu.dp].engine.neighbor.latest()
        copy = None if snap is None or snap.iteration != clu.iteration \
            else np.asarray(snap.state["shard"]).reshape(-1)
        if copy is None or copy.size != sl.stop - sl.start:
            bad += sl.stop - sl.start
        else:
            held.append((sl, copy.view(np.uint32)))
    for x, lo in zip(leaves, starts):
        hi, dev = lo + x.size, None
        for sl, copy in held:
            a, b = max(lo, sl.start), min(hi, sl.stop)
            if a < b:
                if dev is None:
                    dev = np.asarray(x).reshape(-1).view(np.uint32)
                bad += int(np.count_nonzero(
                    dev[a - lo:b - lo] != copy[a - sl.start:b - sl.start]))
    return bad


def device_peak(stats: Dict[str, int]) -> int:
    """The chip's peak memory from its `memory_stats()`: the peak of the
    arrays held plus the peak the runtime reserved for running programs.
    The TPU runtime books a program's temporaries (XLA's temp) as reserved,
    not in use, so `peak_bytes_in_use` alone leaves out the step's
    activations."""
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def _first_grad(scale: float) -> Callable:
    """Leaf norms and sketch of the first gradient, from Adam's first
    moment after one step (m = (1 - b1) g)."""
    def read(m):
        g = jax.tree.map(lambda x: x / scale, m)
        return reference.leaf_norms(g), reference.sketch(g)
    return jax.jit(read)


def _change_norms(ref, arch) -> Callable:
    return jax.jit(lambda master, key: reference.leaf_norms(jax.tree.map(
        lambda a, b: a - b.astype(jnp.float32), master,
        ref.init_params(arch, key))))


def instrument(clu, spans: list, drive: str) -> None:
    """Host spans around the calls into each layer, on this instance."""
    def wrap(obj, attr, name):
        fn = getattr(obj, attr)

        def wrapped(*a, **k):
            with trace.span(name, spans):
                return fn(*a, **k)
        setattr(obj, attr, wrapped)
    if drive == "loop":
        wrap(clu, "_assemble_batch", "batch")
        wrap(clu, "_step", "train_step")
        wrap(clu, "_shard_and_backup", "instant_ckpt")
        wrap(clu.transport, "run", "fabric_model")
    wrap(clu, "recover", "recover")


# --------------------------------------------------------------------------- #
def run(cell: Cell, seed: int, seconds: float, traced: bool,
        t_start: float, peak_flops: float) -> Dict[str, Any]:
    """Set up, measure for `seconds`, check. Returns the record, the
    numbers compared and the device's peak memory."""
    arch, tr, tf = cell.config["arch"], cell.config["train"], cell.traffic
    rec = Record(tokens_per_step=tr["global_batch"] * tr["seq_len"],
                 flops_per_token=cell.ref.flops_per_token(arch, tr["seq_len"]),
                 peak_flops=peak_flops, compile_log=probes.CompileLog())
    b1 = tr["hp"]["b1"]
    changed = _change_norms(cell.ref, arch)
    with tempfile.TemporaryDirectory(prefix="bench_ckpt") as tmp:
        clu, key, src = build(cell, seed, tmp)
        step = stepper(clu, tf["drive"])

        def fed_step() -> Tuple[float, List[int]]:
            n0 = len(src.log)
            return step(), src.log[n0:]
        # set-up: the first three steps, through the window's own call and
        # feed, are the ones the reference follows
        losses, fed = [], []
        for k in range(3):
            loss, rows = fed_step()
            losses.append(loss)
            fed.append(rows)
            if k == 0:
                grads, sk = _first_grad(1 - b1)(clu.state["opt"]["m"])
        prog = reference.Readings(
            losses, np.asarray(grads), np.asarray(sk),
            {3: np.asarray(changed(clu.state["opt"]["master"], key))})
        kill = tf.get("kill")
        state_bad = 0
        if kill:
            from repro.runtime.cluster import FaultScript
            _fingerprint(clu.state).block_until_ready()
        if traced:
            instrument(clu, rec.spans, tf["drive"])
        rec.setup_s = time.perf_counter() - t_start

        with (trace.recording() if traced
              else contextlib.nullcontext([])) as events:
            with trace.span(trace.WINDOW_SPAN):
                t0 = time.perf_counter()
                while time.perf_counter() - t0 < seconds:
                    if not kill:
                        step()
                        rec.steps += 1
                        continue
                    reached = clu.iteration
                    before = np.asarray(_fingerprint(clu.state))
                    t_kill = time.perf_counter()
                    clu.inject_failure(kill["workers"])
                    report = clu.recover(FaultScript())
                    after = np.asarray(_fingerprint(clu.state))
                    state_bad += int(np.sum(before != after)) \
                        + report.rolled_back_iterations
                    resumed = []
                    while clu.iteration <= reached:
                        resumed.append(fed_step())
                        rec.steps += 1
                    rec.kills.append((t_kill, time.perf_counter()))
                    if len(rec.kills) == 1 and resumed:
                        # the first kill follows set-up: the reference
                        # follows its first resumed step as step 4
                        prog.losses.append(resumed[0][0])
                        fed.append(resumed[0][1])
                        prog.changes[len(fed)] = np.asarray(
                            changed(clu.state["opt"]["master"], key))
                rec.window = (t0, time.perf_counter())
        if traced:
            rec.trace = trace.reduce(events)
        rec.host_rss_bytes = probes.host_peak_rss()
        stats = jax.devices()[0].memory_stats() or {}
        numbers = {}
        if tf["drive"] == "loop":
            numbers["held_copy_mismatch"] = held_copy_mismatches(clu)
        if kill:
            numbers["restored_state_mismatch"] = state_bad
        for w in clu.workers:
            w.engine.close()
        for x in jax.tree.leaves(clu.state):
            x.delete()
        del clu, step
        gc.collect()

    rows = [r for f in fed for r in f]
    numbers["rows_repeated"] = len(rows) - len(set(rows)) + sum(
        abs(len(f) - tr["global_batch"]) for f in fed)
    batches = [np.stack([src.row(i) for i in f]) for f in fed]
    ref = reference.train(cell.ref, arch, tr["hp"], key, batches,
                          rows=cell.config["reference_rows"],
                          changes=tuple(prog.changes))
    numbers.update(reference.compare(prog, ref))
    return {"record": rec, "numbers": numbers,
            "memory_peak_bytes": device_peak(stats)}
