"""The profiler's trace of the measured window, and its reduction.

A traced run records the window with `jax.profiler`, with the host side cut
down to the benchmark's own `TraceAnnotation` spans, so that host spans and
device operations share one clock. The reduction works on plain event
tuples, so a test can check it on a small recorded trace.
"""
from __future__ import annotations

import contextlib
import glob
import os
import tempfile
import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

# (plane, line, name, start_ns, duration_ns)
Event = Tuple[str, str, str, float, float]

DEVICE_PLANE = "/device:TPU:"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
WINDOW_SPAN = "window"
TOP = 10                          # entries of each list in the breakdown


@contextlib.contextmanager
def span(name: str, spans: Optional[List[Tuple[str, float, float]]] = None):
    """A host span: in the profiler's trace when one is being taken, and in
    `spans` on the `time.perf_counter` clock."""
    import jax
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        yield
    if spans is not None:
        spans.append((name, t0, time.perf_counter()))


@contextlib.contextmanager
def recording() -> Iterator[List[Event]]:
    """Trace what runs inside; the events are in the yielded list once the
    block has ended."""
    import jax
    events: List[Event] = []
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # the Python tracer would swamp the host
    opts.host_tracer_level = 1       # user annotations only
    with tempfile.TemporaryDirectory(prefix="bench_trace") as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            yield events
        finally:
            jax.profiler.stop_trace()
        for path in glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                              recursive=True):
            events.extend(load_events(path))


def load_events(path: str) -> List[Event]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    return [(plane.name, line.name, ev.name, float(ev.start_ns),
             float(ev.duration_ns))
            for plane in data.planes for line in plane.lines
            for ev in line.events]


def _union(intervals: Sequence[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


@dataclass
class Reduced:
    """A trace reduced to what the metrics read. Times in seconds."""
    window_s: float
    busy_s: float                  # union of op intervals, mean over chips
    chips: int
    op_seconds: Dict[str, float]   # op name -> self time, over chips
    module_seconds: Dict[str, float]
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)
    # the TOP longest idle gaps of the first chip, named by host span


def reduce(events: Sequence[Event]) -> Optional[Reduced]:
    """Busy and idle time of the devices inside the `window` host span, the
    device ops that took the most time, and the idle gaps of the first chip,
    each named by the host span that overlaps it most. None where the trace
    holds no window or no device operation."""
    windows = [(s, s + d) for p, _, n, s, d in events
               if n == WINDOW_SPAN and not _is_device(p)]
    if not windows:
        return None
    w_lo, w_hi = windows[0]
    by_chip: Dict[str, List[Tuple[float, float, str]]] = defaultdict(list)
    modules: Dict[str, float] = defaultdict(float)
    host: List[Tuple[str, float, float]] = []
    for plane, line, name, s, d in events:
        lo, hi = max(s, w_lo), min(s + d, w_hi)
        if hi <= lo:
            continue
        if _is_device(plane):
            if line == OP_LINE:
                by_chip[plane].append((lo, hi, _short(name)))
            elif line == MODULE_LINE:
                modules[name] += (hi - lo) / 1e9
        elif name != WINDOW_SPAN:
            host.append((name, lo, hi))
    if not by_chip:
        return None
    ops: Dict[str, float] = defaultdict(float)
    for iv in by_chip.values():
        for name, sec in _self_times(iv).items():
            ops[name] += sec
    busy = {p: _union([(lo, hi) for lo, hi, _ in iv])
            for p, iv in by_chip.items()}
    busy_s = sum(sum(hi - lo for lo, hi in u) for u in busy.values()) \
        / len(busy) / 1e9
    first = busy[sorted(busy)[0]]
    edges = [w_lo] + [x for iv in first for x in iv] + [w_hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [(_cover(host, lo, hi), (hi - lo) / 1e9)
             for lo, hi in gaps[:TOP]]
    return Reduced((w_hi - w_lo) / 1e9, busy_s, len(busy), dict(ops),
                   dict(modules), named)


def _short(name: str) -> str:
    """An op's instruction name ("fusion.12") out of its HLO text."""
    return name.split(" = ")[0].lstrip("%")


def _self_times(ops: Sequence[Tuple[float, float, str]]) -> Dict[str, float]:
    """Seconds of each op not covered by the ops nested in it (a while
    loop's body runs inside the loop's own event)."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List] = []                      # [hi, name, self_ns]

    def close(entry):
        out[entry[1]] += entry[2] / 1e9
    for lo, hi, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= lo:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(hi, stack[-1][0]) - lo
        stack.append([hi, name, hi - lo])
    for entry in stack:
        close(entry)
    return dict(out)


def _is_device(plane: str) -> bool:
    return plane.startswith(DEVICE_PLANE)


def _cover(host: Sequence[Tuple[str, float, float]], lo: float,
           hi: float) -> str:
    """The host span that overlaps [lo, hi) the most (the innermost, for
    nested spans of equal overlap), or "other"."""
    best, best_len, best_dur = "other", 0.0, float("inf")
    for name, s, e in host:
        ov = min(e, hi) - max(s, lo)
        if ov > best_len or (ov == best_len and ov > 0 and e - s < best_dur):
            best, best_len, best_dur = name, ov, e - s
    return best


def breakdown(red: Reduced) -> Dict[str, list]:
    """The device ops that took the most time (self time, over the chips)
    and the longest idle gaps, for the result line."""
    ops = sorted(red.op_seconds.items(), key=lambda kv: -kv[1])[:TOP]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in red.idle_gaps]}
