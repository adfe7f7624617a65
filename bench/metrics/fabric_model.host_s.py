"""Host seconds per step in the modeled fabric: the span around the loop's
`transport.run` (the event loop of `core/lccl.py` and the delivery CRCs of
`ckpt/stream.py`)."""


def read(rec):
    s = sum(hi - lo for n, lo, hi in rec.spans if n == "fabric_model")
    return s / rec.steps if s > 0 and rec.steps else None
