"""Host seconds per kill of moving the failed worker's shard: the program's
spans `recover.stream` (the holder's copy re-chunked and CRC'd, the fabric
drained with a CRC check on delivery, the shard reassembled into the
optimizer vector)."""
from bench.program_spans import per_kill


def read(rec):
    return per_kill(rec, "recover.stream")
