"""Share (%) of the instant checkpoint's bytes whose chunk CRCs were taken
on the program's pool of host threads: the `parallel_bytes` counter of the
span `stream.chunk` under `ckpt.instant` over its `bytes`, in the window of
a run without kills. A program whose `stream.chunk` counts no
`parallel_bytes` reads as nothing."""
from bench.program_spans import _recorded, per_step


def read(rec):
    if not any("parallel_bytes" in s.counts for s in _recorded() or ()
               if s.name == "stream.chunk"):
        return None
    cut = per_step(rec, "stream.chunk", under="ckpt.instant", key="bytes")
    if not cut:
        return None
    pooled = per_step(rec, "stream.chunk", under="ckpt.instant",
                      key="parallel_bytes") or 0.0
    return 100.0 * pooled / cut
