"""Serving loop: device idle milliseconds under the program's segment
boundary spans (``batcher.sync``, ``batcher.harvest``, ``batcher.evict``,
``batcher.deadlines``), per ``batcher.segment`` span started in the
window; ``bench/spans.py`` reads the run's trace."""
from bench import spans

BOUNDARY = ("batcher.sync", "batcher.harvest", "batcher.evict",
            "batcher.deadlines")


def read(rec):
    if rec["kind"] != "serve":
        return None
    return spans.idle_ms_per(rec, BOUNDARY, "batcher.segment")
