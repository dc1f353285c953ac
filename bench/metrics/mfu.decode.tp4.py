"""Model step over a mesh: model operations of the window (every decoded
token and every prompt prefilled, ``bench/flops.py``) over the window's
seconds times the peak of all the chips the run spans (the record's
``chips``), in percent."""
from bench import flops, readout, reference_gqa


def read(rec):
    if rec["kind"] != "serve" or not rec.get("trace") \
            or not rec.get("chips"):
        return None
    sz = reference_gqa.sizes_of(rec["config"])
    w = rec["serve"]["window"]
    ops = w["row_steps"] * flops.decode_flops(sz) + sum(
        k * flops.prefill_flops(sz, p) for p, k in w["prefills"])
    return 100.0 * ops / (rec["window_s"] * rec["chips"]
                          * readout.peak(rec)["flops_per_s"])
