"""GB per step copied from the device to the host by the instant
checkpoint: the `bytes` counter of the program's span `opt.d2h` under
`ckpt.instant` (the whole optimizer state, 12 B a parameter)."""
from bench.program_spans import per_step


def read(rec):
    v = per_step(rec, "opt.d2h", under="ckpt.instant", key="bytes")
    return v / 1e9 if v else None
