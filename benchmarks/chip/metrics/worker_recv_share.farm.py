"""Share of the traced stretch that the worker host spends in ``recv`` spans
of its cut channels (the ``jaxmesh`` transport)."""


def read(r):
    spans = r.data.get("host_spans")
    if not spans:
        return None
    lo, hi = r.data["trace_window"]
    w = r.data["worker_device"]
    t = sum(max(0.0, min(e, hi) - max(s, lo))
            for h, name, s, e in spans if h == w and name == "recv")
    return 100.0 * t / (hi - lo)
