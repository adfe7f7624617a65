"""Seconds from the start of the process to the start of the window:
runtime start, building the loop and its state, and the first three steps,
which compile or load every program the window runs."""


def read(rec):
    return rec.setup_s
