"""Serving loop: active row-steps (the segments' ``emitted`` flags) over
slots x decode steps dispatched in the window, in percent."""


def read(rec):
    if rec["kind"] != "serve":
        return None
    w = rec["serve"]["window"]
    return 100.0 * w["row_steps"] / w["slot_steps"] if w["slot_steps"] \
        else None
