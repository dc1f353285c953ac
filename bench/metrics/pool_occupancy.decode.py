"""Serving loop: row-steps emitted over slots x steps dispatched, the sums
of the ``emitted`` and ``slot_steps`` arguments of the program's
``batcher.harvest`` spans in the window, in percent; ``bench/spans.py``
reads the run's trace."""
from bench import spans


def read(rec):
    if rec["kind"] != "serve":
        return None
    s = spans.summary(rec)
    t = s and s["spans"].get("batcher.harvest")
    if not t or not t["args"].get("slot_steps"):
        return None
    return 100.0 * t["args"]["emitted"] / t["args"]["slot_steps"]
