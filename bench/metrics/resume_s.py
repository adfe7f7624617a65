"""Seconds from a kill to the end of the first step that takes the job past
the iteration it had reached before the kill, so steps rolled back are paid
in full; the mean over the kills of the window. The modeled detection time
is not in it: the kill is where the clock starts."""


def read(rec):
    if not rec.kills:
        return None
    return sum(hi - lo for lo, hi in rec.kills) / len(rec.kills)
