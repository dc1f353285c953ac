"""Model step: model operations of the window (every decoded token and
every prompt prefilled, ``bench/flops.py``) over the window's seconds times
the chip's peak, in percent."""
from bench import flops, readout


def read(rec):
    if rec["kind"] != "serve" or not rec.get("trace"):
        return None
    sz = readout.sizes(rec)
    w = rec["serve"]["window"]
    ops = w["row_steps"] * flops.decode_flops(sz) + sum(
        k * flops.prefill_flops(sz, p) for p, k in w["prefills"])
    return 100.0 * ops / (rec["window_s"] * readout.peak(rec)["flops_per_s"])
