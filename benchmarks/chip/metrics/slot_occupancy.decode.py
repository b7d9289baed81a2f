"""Mean share of slots active over the engine's ``decode_chunk`` spans in the
window."""

import numpy as np


def read(r):
    v = r.data.get("occupancy")
    return 100.0 * float(np.mean(v)) if v else None
