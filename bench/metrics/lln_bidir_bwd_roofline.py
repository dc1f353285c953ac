"""Kernels: share of their roofline of the bidirectional LLN backward
kernels (``kernels/lln_backward.py:_bidir_dq_kernel``,
``_bidir_reduce_kernel`` and ``_bidir_dkv_kernel``), at the train step's
shapes, in percent.  Found in the trace by their shapes: dq takes qs, g, o
(rows, n, d), the normaliser (rows, 1, n) and the state; reduce takes the
first four and gives dS, dz; dkv takes ks, v, dS, dz and gives dk, dv."""
from bench import flops, readout


def read(rec):
    if rec["kind"] != "train":
        return None
    sz = readout.sizes(rec)
    n, d = rec["mix"]["seq"], sz["head_dim"]
    bh, bg = rec["mix"]["batch"] * sz["heads"], \
        rec["mix"]["batch"] * sz["kv_heads"]
    q_side = [(bh, n, d), (bh, n, d), (bh, n, d), (bh, 1, n)]
    state = [(bg, d, d), (bg, 1, d)]
    return readout.roofline(rec, [
        (q_side + state, [(bh, n, d)], flops.bidir_dq_call(bh, n, d)),
        (q_side, state, flops.bidir_reduce_call(bh, n, d)),
        ([(bg, n, d), (bg, n, d)] + state, [(bg, n, d), (bg, n, d)],
         flops.bidir_dkv_call(bg, n, d))])
