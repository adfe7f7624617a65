"""Tokens trained per second over the whole window: every token of every
step, over the time from the window's start to the end of the last step
that started inside it. Cells that kill workers report `resume_s`."""


def read(rec):
    if rec.kills or not rec.steps:
        return None
    lo, hi = rec.window
    return rec.steps * rec.tokens_per_step / (hi - lo)
