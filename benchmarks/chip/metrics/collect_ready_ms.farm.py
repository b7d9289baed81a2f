"""Time the collect host waits for a chunk's results to be ready (``ready``
spans: the render and the copy between chips) per batch, in the traced
stretch of the window."""

from harness import common

_spans = common.load_module("metrics", "_collect_spans")


def read(r):
    return _spans.ms_per_batch(r, "ready")
