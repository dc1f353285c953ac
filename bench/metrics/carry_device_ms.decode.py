"""Pool programs: device milliseconds per execution of the decode segment
program (``jit__segment``) of the ops under none of the program's model
scopes (``embed``, ``attn``, ``mlp``, ``lm_head``, ``sample``,
``sentinel``): the layer scan's slicing and stacking of the caches and
XLA's copies of the carry; ``bench/spans.py`` reads the run's trace."""
from bench import spans


def read(rec):
    if rec["kind"] != "serve":
        return None
    s = spans.summary(rec)
    seg = s and s["segment"]
    if not seg or not seg["executions"] or not seg["scoped_s"]:
        return None
    return 1e3 * seg["residual_s"] / seg["executions"]
