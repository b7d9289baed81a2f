"""Output tokens emitted in the window over the window's seconds."""


def read(r):
    if "tokens_in_window" not in r.data:
        return None
    return r.data["tokens_in_window"] / r.data["window_s"]
