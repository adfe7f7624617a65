"""Model FLOP/s utilization, in percent: the forward and backward FLOPs a
token needs (the configuration's `flops_per_token`, nothing recomputed)
times the tokens per second of `tokens_per_s`, over the chip's bf16 peak
(`bench/peaks.py`)."""


def read(rec):
    if rec.kills or not rec.steps:
        return None
    lo, hi = rec.window
    rate = rec.steps * rec.tokens_per_step / (hi - lo)
    return 100.0 * rate * rec.flops_per_token / rec.peak_flops
