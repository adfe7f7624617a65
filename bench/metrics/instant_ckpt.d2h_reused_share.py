"""Share (%) of the instant checkpoint's copy to the host that landed in a
host vector already mapped, handed out again by the program's pool: the
`reused_bytes` counter of the span `opt.d2h` under `ckpt.instant` over its
`bytes`, in the window of a run without kills. A program whose `opt.d2h`
counts no `reused_bytes` reads as nothing."""
from bench.program_spans import _recorded, per_step


def read(rec):
    if not any("reused_bytes" in s.counts for s in _recorded() or ()
               if s.name == "opt.d2h"):
        return None
    moved = per_step(rec, "opt.d2h", under="ckpt.instant", key="bytes")
    if not moved:
        return None
    reused = per_step(rec, "opt.d2h", under="ckpt.instant",
                      key="reused_bytes") or 0.0
    return 100.0 * reused / moved
