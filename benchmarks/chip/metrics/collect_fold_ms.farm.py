"""Time the collect host spends folding on the host (``fold`` spans: the
Collect's per-item loop) per batch, in the traced stretch of the window."""

from harness import common

_spans = common.load_module("metrics", "_collect_spans")


def read(r):
    return _spans.ms_per_batch(r, "fold")
