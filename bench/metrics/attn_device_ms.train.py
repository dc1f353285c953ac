"""Model step: device milliseconds per train step of the ops under the
program's ``attn`` scope (the block's norm, q/k/v, attention and output
projection: forward, backward and recompute); ``bench/spans.py`` reads the
run's trace."""
from bench import spans


def read(rec):
    return spans.scope_ms_per_step(rec, "attn")
