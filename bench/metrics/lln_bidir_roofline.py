"""Kernels: share of their roofline of the bidirectional LLN forward
kernels (``kernels/lln_attention.py:_lln_reduce_kernel`` and
``_lln_apply_kernel``), at the train step's shapes, in percent.  Found in
the trace by their shapes: reduce takes ks, v (rows, n, d) and gives the
state (rows, d, d), (rows, 1, d); apply takes qs and the state and gives
the output, with or without its normaliser (rows, 1, n)."""
from bench import flops, readout


def read(rec):
    if rec["kind"] != "train":
        return None
    sz = readout.sizes(rec)
    n, d = rec["mix"]["seq"], sz["head_dim"]
    bh, bg = rec["mix"]["batch"] * sz["heads"], \
        rec["mix"]["batch"] * sz["kv_heads"]
    state = [(bg, d, d), (bg, 1, d)]
    apply = flops.lln_apply_call(bh, n, d)
    return readout.roofline(rec, [
        ([(bg, n, d), (bg, n, d)], state, flops.lln_reduce_call(bg, n, d)),
        ([(bh, n, d)] + state, [(bh, n, d)], apply),
        ([(bh, n, d)] + state, [(bh, n, d), (bh, 1, n)], apply)])
