"""Share of the worker chip's idle time, in the traced stretch of the
window, during which the collect host is inside a ``fold`` span.  Idle time
is what the union of the chip's program executions leaves; both sides are
on the host clock."""

from harness import common, tracing

_spans = common.load_module("metrics", "_collect_spans")


def read(r):
    folds = _spans.clipped(r, "fold")
    if folds is None or not r.trace_data:
        return None
    dev = tracing.device(r.trace_data, r.data["worker_device"])
    if dev is None or not dev["programs"]:
        return None
    lo, hi = r.data["trace_window"]
    idle = tracing.gaps(dev, lo, hi)
    idle_s = sum(e - s for s, e in idle)
    if idle_s <= 0:
        return None
    overlap = sum(max(0.0, min(e, fe) - max(s, fs))
                  for s, e in idle for fs, fe in tracing.union(folds))
    return 100.0 * overlap / idle_s
