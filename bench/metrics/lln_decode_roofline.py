"""Kernels: share of its roofline of the LLN decode kernel
(``kernels/lln_attention.py:lln_decode_pallas``, one call per layer per
decode step over every slot, T padded to 16): least time from its shapes
over its device time, in percent.  Bound by HBM bandwidth: each call reads
and writes every slot's (64 x 64) float32 state per head.  Found in the
trace by its shapes: qs, ks, v (rows, 16, d) and the state (rows, d, d),
(rows, 1, d) in; the output and the new state out."""
from bench import flops, readout


def read(rec):
    if rec["kind"] != "serve":
        return None
    sz = readout.sizes(rec)
    slots = rec["mix"]["pool"]["slots"]
    bh, bg = slots * sz["heads"], slots * sz["kv_heads"]
    t, d = 16, sz["head_dim"]
    state = [(bh, d, d), (bh, 1, d)]
    return readout.roofline(rec, [(
        [(bh, t, d), (bg, t, d), (bg, t, d)] + state, [(bh, t, d)] + state,
        flops.lln_decode_call(bh, bg, t, d))])
