"""Model step: 3 x (2 x parameters + attention) operations per token of
every step in the window (no rematerialisation counted), over the window's
seconds times the chip's peak, in percent."""
from bench import flops, readout


def read(rec):
    if rec["kind"] != "train" or not rec.get("trace"):
        return None
    t = rec["train"]
    ops = t["steps"] * flops.train_flops(readout.sizes(rec),
                                         t["tokens_per_step"])
    return 100.0 * ops / (rec["window_s"] * readout.peak(rec)["flops_per_s"])
