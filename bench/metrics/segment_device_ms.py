"""Pool programs: device milliseconds per execution of the pool's decode
segment program (``make_pool_setup``'s ``_segment``; in the trace's "XLA
Modules" line as ``jit__segment``)."""
from bench import readout


def read(rec):
    found = readout.device_seconds(rec, "programs", "_segment")
    if found is None or not found[0]:
        return None
    return 1e3 * found[1] / found[0]
