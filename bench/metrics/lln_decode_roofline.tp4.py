"""Kernels over a mesh: share of its roofline of the LLN decode kernel
(``kernels/lln_attention.py:lln_decode_pallas``) at the shapes each chip
runs it at: the pool's slots times the query heads a chip holds (yi-9b at
model=4: 32 x 8 = 256 rows) over the slots times its kv heads (32 x 1),
T padded to 16, head size 128, as a four-chip trace records them.  Least
time from those shapes (``flops.lln_decode_call``) over the kernel's
device time per chip, in percent; bound by HBM bandwidth (each call reads
and writes every row's 128 x 128 float32 state)."""
from bench import flops, readout, reference_gqa


def read(rec):
    if rec["kind"] != "serve" or not rec.get("chips"):
        return None
    sz = reference_gqa.sizes_of(rec["config"])
    chips, slots = rec["chips"], rec["mix"]["pool"]["slots"]
    bh = slots * sz["heads"] // chips
    bg = slots * sz["kv_heads"] // chips
    t, d = 16, sz["head_dim"]
    state = [(bh, d, d), (bh, 1, d)]
    return readout.roofline(rec, [(
        [(bh, t, d), (bg, t, d), (bg, t, d)] + state, [(bh, t, d)] + state,
        flops.lln_decode_call(bh, bg, t, d))])
