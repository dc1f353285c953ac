"""Pool programs over a mesh: device milliseconds per execution of the
decode segment program (``jit__segment``) in collective operations
(all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all),
per device; the record's ``collectives`` splits it by the model scope of
the op each collective partitions (``bench/collectives.py``)."""
from bench import collectives


def read(rec):
    if rec["kind"] != "serve":
        return None
    got = rec.get("collectives") or collectives.summary(rec)
    if not got or not got["executions"] or not got["calls"]:
        return None
    return 1e3 * got["collective_s"] / got["executions"]
