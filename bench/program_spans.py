"""The program's own host spans (`repro.launch.spans`), for the per-layer
metrics that read them. Each metric keeps the spans of one name that lie
inside the measured window (per step) or inside the kills (per kill),
optionally only those under a span of another name (an ancestor). A
program without the recorder reads as nothing."""
from __future__ import annotations

from typing import Optional, Sequence, Tuple


def _recorded() -> Optional[list]:
    try:
        from repro.launch.spans import spans
    except ImportError:
        return None
    return spans()


def total(name: str, intervals: Sequence[Tuple[float, float]],
          under: Optional[str] = None, key: Optional[str] = None
          ) -> Optional[float]:
    """Seconds (or counter `key`) of the spans `name` inside `intervals`,
    under a span `under` when given; None without the recorder."""
    recorded = _recorded()
    if recorded is None:
        return None
    by_sid = {s.sid: s for s in recorded}

    def has_ancestor(s) -> bool:
        while s.parent is not None and s.parent in by_sid:
            s = by_sid[s.parent]
            if s.name == under:
                return True
        return False
    out = 0.0
    for s in recorded:
        if s.name != name or not any(lo <= s.t0 and s.t1 <= hi
                                     for lo, hi in intervals):
            continue
        if under is not None and not has_ancestor(s):
            continue
        out += s.t1 - s.t0 if key is None else s.counts.get(key, 0)
    return out


def per_step(rec, name: str, under: Optional[str] = None,
             key: Optional[str] = None) -> Optional[float]:
    """`total` over the window of a run without kills, per step."""
    if rec.kills or not rec.steps:
        return None
    v = total(name, [rec.window], under, key)
    return v / rec.steps if v else None


def per_kill(rec, name: str, under: Optional[str] = None,
             key: Optional[str] = None) -> Optional[float]:
    """`total` over the kills of a run, per kill."""
    if not rec.kills:
        return None
    v = total(name, rec.kills, under, key)
    return v / len(rec.kills) if v else None

