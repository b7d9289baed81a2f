"""Mean over the traced steps of the largest count of the program's
``held_expert_tokens`` counter (tokens routed to each held expert of each
expert layer in the step) over the counts' mean: 1 is an even load."""

import numpy as np


def read(r):
    counts = [np.asarray(h) for _, _, h in r.data.get("traced_steps", ())
              if h is not None]
    ratios = [np.max(c) / np.mean(c) for c in counts if np.mean(c) > 0]
    return float(np.mean(ratios)) if ratios else None
