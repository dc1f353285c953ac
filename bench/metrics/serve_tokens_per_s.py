"""Output tokens in every request's output at the first segment boundary
past the window's end, over the seconds from the window's opening to that
boundary (first tokens from prefill included)."""


def read(rec):
    if rec["kind"] != "serve":
        return None
    return rec["serve"]["window"]["tokens"] / rec["window_s"]
