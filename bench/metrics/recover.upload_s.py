"""Host seconds per kill of putting the restored state on the device: the
program's span `recover.upload` (the vector unflattened, each leaf copied
to the device, the parameters cast from the master copy; the programs this
builds are compiled or loaded inside it)."""
from bench.program_spans import per_kill


def read(rec):
    return per_kill(rec, "recover.upload")
