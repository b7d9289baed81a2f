"""Device time of the programs on the worker chip (the band renders) per
batch, in the traced stretch of the window."""

from harness import tracing


def read(r):
    if not r.trace_data or not r.data.get("traced_batches"):
        return None
    dev = tracing.device(r.trace_data, r.data["worker_device"])
    if dev is None:
        return None
    lo, hi = r.data["trace_window"]
    t, n = tracing.program_time(dev, "", lo, hi)
    return 1e3 * t / r.data["traced_batches"] if n else None
