"""Reductions that several per-layer metrics share; each metric's file under
``metrics/`` names the one it reads."""

from __future__ import annotations

import numpy as np

from harness import costs, tracing


def idle_share(r):
    """One minus the union of the device's program executions over the
    traced stretch of the window (the worker chip where there are several)."""
    if not r.trace_data:
        return None
    dev = tracing.device(r.trace_data, r.data.get("worker_device", 0))
    if dev is None or not dev["programs"]:
        return None
    lo, hi = r.data["trace_window"]
    return 100.0 * (1.0 - tracing.busy_s(dev, lo, hi) / (hi - lo))


def itl_p95_ms(r):
    """95th percentile of the gaps between consecutive output tokens of every
    request whose first token came in the window (host clock, per step)."""
    v = r.data.get("itl_s")
    return 1e3 * float(np.percentile(v, 95)) if v else None


def mfu(r):
    """The whole served path's share of the chip's bf16 peak over the
    window: operations the model needs for every prompt token prefilled (no
    output head) and every output token (with it), attention over each
    token's context included, over the window's seconds times the peak."""
    if r.peak is None or "decodes" not in r.data:
        return None
    lo, hi = r.data["window"]
    flops = 0
    for ts, ctx in r.data["decodes"]:
        if lo <= ts < hi:
            flops += sum(costs.token_flops(r.cfg, c) for c in ctx)
    for ts, c0, n in r.data["prefills"]:
        if lo <= ts < hi:
            flops += sum(costs.token_flops(r.cfg, c0 + j, logits=False)
                         for j in range(1, n + 1))
    if not flops:
        return None
    return 100.0 * flops / ((hi - lo) * float(r.peak["bf16_flops_per_s"]))
