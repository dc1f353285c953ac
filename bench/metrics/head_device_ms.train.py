"""Model step: device milliseconds per train step of the ops under the
program's ``mlm_head`` scope (the final norm, the vocabulary projection
and the masked-token loss, forward and backward); ``bench/spans.py``
reads the run's trace."""
from bench import spans


def read(rec):
    return spans.scope_ms_per_step(rec, "mlm_head")
