"""Median over requests due in the window of the engine's ``admit`` instant
minus the due time: how long a request waits for a slot."""

import numpy as np


def read(r):
    v = r.data.get("queue_wait_s")
    return 1e3 * float(np.median(v)) if v else None
