"""Host seconds per step of cutting the instant checkpoint's shards into
CRC'd chunks: the program's span `stream.chunk` (the CRC32 loop of
`ChunkedStream`) under `ckpt.instant`."""
from bench.program_spans import per_step


def read(rec):
    return per_step(rec, "stream.chunk", under="ckpt.instant")
