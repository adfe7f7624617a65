"""Percent of the traced window in which no operation ran on the device:
one minus the union of the device-op intervals over the window, averaged
over the chips used."""


def read(rec):
    if rec.trace is None or rec.trace.busy_s <= 0:
        return None
    return 100.0 * (1.0 - rec.trace.busy_s / rec.trace.window_s)
