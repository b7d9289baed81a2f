"""Device time per call of the training step's program
(``jit_train_step``), in the traced stretch."""

from harness import tracing

PROGRAM = "jit_train_step"


def read(r):
    if not r.trace_data:
        return None
    dev = tracing.device(r.trace_data, 0)
    if dev is None:
        return None
    lo, hi = r.data["trace_window"]
    t, n = tracing.program_time(dev, PROGRAM, lo, hi)
    return 1e3 * t / n if n else None
