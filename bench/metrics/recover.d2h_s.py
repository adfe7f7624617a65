"""Host seconds per kill of the optimizer state's copy to the host inside
`recover()`: the program's span `opt.d2h` under `recover` (the whole state
read back, then the failed worker's slice overwritten)."""
from bench.program_spans import per_kill


def read(rec):
    return per_kill(rec, "opt.d2h", under="recover")
