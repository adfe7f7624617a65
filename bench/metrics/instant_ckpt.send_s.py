"""Host seconds per step of handing the instant checkpoint's chunks to the
modeled fabric: the program's spans `stream.send` (`CkptEngine._stream`'s
`transport.send`) and `stream.withdraw` (a stale stream's unsent chunks
taken back) under `ckpt.instant`."""
from bench.program_spans import per_step


def read(rec):
    parts = [per_step(rec, name, under="ckpt.instant")
             for name in ("stream.send", "stream.withdraw")]
    if parts[0] is None:
        return None
    return parts[0] + (parts[1] or 0.0)
