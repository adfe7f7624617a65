"""Device seconds per step of the loop's jitted train step (`loop_step`,
module `jit_step` in the trace), over the steps of the traced window."""


def read(rec):
    if rec.trace is None or not rec.steps:
        return None
    s = sum(v for k, v in rec.trace.module_seconds.items()
            if k.startswith("jit_step"))
    return s / rec.steps if s > 0 else None
