"""Spans of the farm's collect host (``core/stream.py`` ``_retire``): what
the ``collect_*`` and ``idle_in_fold_share`` readers share.  Not a metric."""


def clipped(r, name: str):
    """``(start, end)`` of the collect host's ``name`` spans, clipped to the
    traced stretch, or ``None`` where it has none there."""
    rows = r.data.get("host_spans")
    if not rows:
        return None
    lo, hi = r.data["trace_window"]
    host = r.cfg["assignment"]["collect"]
    out = [(max(s, lo), min(e, hi)) for h, n, s, e in rows
           if h == host and n == name and min(e, hi) > max(s, lo)]
    return out or None


def ms_per_batch(r, name: str):
    """Milliseconds a traced batch that the collect host spends in ``name``
    spans."""
    spans = clipped(r, name)
    n = r.data.get("traced_batches")
    if spans is None or not n:
        return None
    return 1e3 * sum(e - s for s, e in spans) / n
