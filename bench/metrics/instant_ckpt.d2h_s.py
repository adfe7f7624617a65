"""Host seconds per step of the optimizer state's copy to the host in the
instant checkpoint: the program's span `opt.d2h` (`_flatten_opt`, every
leaf read back and written into one float32 vector) under `ckpt.instant`."""
from bench.program_spans import per_step


def read(rec):
    return per_step(rec, "opt.d2h", under="ckpt.instant")
