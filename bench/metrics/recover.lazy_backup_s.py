"""Host seconds per kill of the lazy backup inside `recover()`: the
program's span `recover.lazy_backup` (rank 0's parameters written to an
npz file, then chunked, CRC'd and put on the modeled fabric)."""
from bench.program_spans import per_kill


def read(rec):
    return per_kill(rec, "recover.lazy_backup")
