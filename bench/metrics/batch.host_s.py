"""Host seconds per step of building the batch: the program's spans
`data.batch` (`PrefetchingLoader.get`, one per worker), in the loop and in
the bare step alike."""
from bench.program_spans import per_step


def read(rec):
    return per_step(rec, "data.batch")
