"""What the benchmark reads off the process itself: the chips JAX found, the
executables it built, and the host's peak resident set."""
from __future__ import annotations

import resource
import time
from typing import List, Sequence, Tuple


class NoChip(SystemExit):
    """The run needs chips that this machine does not have."""


def tpu_devices(need: int):
    """The TPU devices, or `NoChip` naming the platform JAX found."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"bench: needs a TPU; JAX found platform "
                     f"{devs[0].platform!r} ({len(devs)} device(s))")
    if len(devs) < need:
        raise NoChip(f"bench: needs {need} TPU chips; found {len(devs)}")
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


def host_peak_rss() -> int:
    """Peak resident set of this process so far, in bytes (ru_maxrss)."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
