"""Kernels: share of their roofline of the block-diagonal softmax kernels
of the train step (``kernels/block_diag.py:_block_diag_kernel`` forward and
``_block_diag_bwd_kernel`` backward), in percent.  Found in the trace by
their shapes: the forward takes q, k, v (rows, n, d) and gives the output;
the backward takes q, k, v and g and gives dq, dk, dv."""
from bench import flops, readout


def read(rec):
    if rec["kind"] != "train":
        return None
    sz = readout.sizes(rec)
    n, d, blk = rec["mix"]["seq"], sz["head_dim"], sz["diag_block"]
    bh, bg = rec["mix"]["batch"] * sz["heads"], \
        rec["mix"]["batch"] * sz["kv_heads"]
    qkv = [(bh, n, d), (bg, n, d), (bg, n, d)]
    return readout.roofline(rec, [
        (qkv, [(bh, n, d)], flops.block_diag_call(bh, bg, n, d, blk)),
        (qkv + [(bh, n, d)], qkv, flops.block_diag_bwd_call(bh, n, d, blk))])
