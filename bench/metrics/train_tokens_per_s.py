"""Tokens of every training step completed in the window, over the
window's seconds (the window ends when the last step's state is ready)."""


def read(rec):
    if rec["kind"] != "train":
        return None
    t = rec["train"]
    return t["steps"] * t["tokens_per_step"] / rec["window_s"]
