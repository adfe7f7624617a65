"""Executables JAX built or loaded from its cache per kill, between the
kill and the resume (a count of `CompileLog` events)."""


def read(rec):
    if not rec.kills:
        return None
    return rec.compile_log.count(rec.kills) / len(rec.kills)
