"""The window (from its start to the last batch's completion) over the
batches completed in it."""


def read(r):
    n = r.data.get("batches")
    return 1e3 * r.data["window_s"] / n if n else None
