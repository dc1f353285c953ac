"""Serving loop: device idle milliseconds under the program's
``batcher.admit`` span and its children (``batcher.prefill``,
``batcher.first_token``, ``batcher.scatter``, ``batcher.resume``), per
request admitted in the window (the sum of the ``rows`` of its
``batcher.prefill`` spans); ``bench/spans.py`` reads the run's trace."""
from bench import spans

ADMIT = ("batcher.admit", "batcher.prefill", "batcher.first_token",
         "batcher.scatter", "batcher.resume")


def read(rec):
    if rec["kind"] != "serve":
        return None
    return spans.idle_ms_per(rec, ADMIT, "batcher.prefill", "rows")
