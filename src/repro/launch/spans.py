"""Host spans and counters of the training loop, the instant checkpoint
and recovery.

    from repro.launch.spans import count, span, spans, summary

    with span("ckpt.instant", iteration=7):
        with span("opt.d2h"):
            ...
            count("bytes", vec.nbytes)

Each `span` opens a `jax.profiler.TraceAnnotation` of the same plain name,
so a profile taken with `jax.profiler` shows it on the host plane, on the
clock of the device ops. It is also kept in memory, always, as a `Span`
on the `time.perf_counter` clock: its parent is the enclosing open span of
the same thread, its `ids` are its own identifiers over those of its
parent (a step's `iteration`, a recovery's number), and its `counts` are
what `count` added while it was the innermost open span. The record is a
ring of the newest `CAPACITY` spans and holds plain numbers only, never an
array, so a long job's host memory does not grow with it.

Spans are host timing and nothing else: no simulator decision reads one.
Wall-clock reads are allowed under `repro/launch/` alone (simlint SIM001),
which is why the recorder lives here.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import Deque, Dict, Iterator, List, NamedTuple, Optional

import jax

CAPACITY = 8192                 # spans kept; the oldest are dropped first


class Span(NamedTuple):
    sid: int                    # this span's number, in order of opening
    name: str
    t0: float                   # time.perf_counter at open and at close
    t1: float
    parent: Optional[int]       # sid of the enclosing open span, if any
    ids: Dict[str, int]
    counts: Dict[str, int]


_record: Deque[Span] = collections.deque(maxlen=CAPACITY)
_sids = itertools.count()
_open = threading.local()       # .stack: [sid, name, t0, ids, counts] lists


def _stack() -> list:
    st = getattr(_open, "stack", None)
    if st is None:
        st = _open.stack = []
    return st


@contextlib.contextmanager
def span(name: str, **ids: int) -> Iterator[None]:
    """Time the block as span `name`, with identifiers `ids` (ints)."""
    st = _stack()
    parent = st[-1] if st else None
    merged = parent[3] if parent else {}
    if ids:
        merged = {**merged, **{k: int(v) for k, v in ids.items()}}
    entry = [next(_sids), name, 0.0, merged, {}]
    st.append(entry)
    try:
        with jax.profiler.TraceAnnotation(name):
            entry[2] = time.perf_counter()
            yield
    finally:
        t1 = time.perf_counter()
        st.pop()
        _record.append(Span(entry[0], name, entry[2], t1,
                            parent[0] if parent else None, merged, entry[4]))


def count(key: str, n: int = 1) -> None:
    """Add `n` to counter `key` of this thread's innermost open span (no
    span open: nothing)."""
    st = _stack()
    if st:
        counts = st[-1][4]
        counts[key] = counts.get(key, 0) + int(n)


def spans(since: Optional[float] = None) -> List[Span]:
    """The closed spans still in the record, in order of closing; with
    `since`, those opened at or after that `time.perf_counter` reading."""
    out = list(_record)
    return out if since is None else [s for s in out if s.t0 >= since]


def summary(since: Optional[float] = None) -> Dict[str, Dict[str, float]]:
    """Per span name: `seconds` in all, `calls`, and each counter summed."""
    out: Dict[str, Dict[str, float]] = {}
    for s in spans(since):
        row = out.setdefault(s.name, {"seconds": 0.0, "calls": 0})
        row["seconds"] += s.t1 - s.t0
        row["calls"] += 1
        for k, v in s.counts.items():
            row[k] = row.get(k, 0) + v
    return out
