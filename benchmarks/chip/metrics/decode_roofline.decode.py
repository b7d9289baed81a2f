"""The decode step's share of its roofline: the least time the chip could
take for the traced stretch's decode calls over their measured device time.

A call's least time is the larger of its operations over peak FLOP/s and its
bytes over peak bandwidth (``harness.costs.decode_step_cost``: each active
row's token through the model, the weights read once at bfloat16, each
row's valid keys and values read and its new ones written).  The host
records each call's active rows and their context lengths; the device trace
gives the program's time.  Reports nothing when the two counts of calls
disagree."""

from harness import costs, tracing

PROGRAM = "jit__decode"


def read(r):
    if not r.trace_data or r.peak is None:
        return None
    dev = tracing.device(r.trace_data, 0)
    if dev is None:
        return None
    lo, hi = r.data["trace_window"]
    t, n = tracing.program_time(dev, PROGRAM, lo, hi)
    calls = [c for ts, c in r.data["decodes"] if lo <= ts < hi]
    if not n or abs(len(calls) - n) > 1:
        return None
    least = 0.0
    bound = {"compute": 0, "memory": 0}
    for ctx in calls[:n]:
        s, b = costs.least_time(*costs.decode_step_cost(r.cfg, ctx), r.peak)
        least += s
        bound[b] += 1
    r.data["decode_bound"] = max(bound, key=bound.get)
    return 100.0 * least / t if t > 0 else None
