"""Device time of the prefill program over the device's busy time, in the
traced stretch of the window."""

from harness import tracing

PROGRAM = "jit__prefill"


def read(r):
    if not r.trace_data:
        return None
    dev = tracing.device(r.trace_data, 0)
    if dev is None:
        return None
    lo, hi = r.data["trace_window"]
    t, n = tracing.program_time(dev, PROGRAM, lo, hi)
    busy = tracing.busy_s(dev, lo, hi)
    return 100.0 * t / busy if n and busy > 0 else None
