"""Host seconds per step in the instant checkpoint: the span around
`SimCluster._shard_and_backup` (the optimizer state's copy to the host in
`_flatten_opt`, its shards, `CkptEngine.on_step` and the chunking)."""


def read(rec):
    s = sum(hi - lo for n, lo, hi in rec.spans if n == "instant_ckpt")
    return s / rec.steps if s > 0 and rec.steps else None
