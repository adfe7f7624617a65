"""Host seconds per kill inside `SimCluster.recover()`: lazy backup, plan,
the stream from the neighbour's held copy, and the restored state's upload
to the device."""


def read(rec):
    s = [hi - lo for n, lo, hi in rec.spans if n == "recover"]
    return sum(s) / len(s) if s else None
