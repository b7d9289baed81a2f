"""90th percentile over every request due in the window of first token minus
due time; a request never answered counts at the wait it was given."""

import numpy as np


def read(r):
    v = r.data.get("ttft_s")
    return 1e3 * float(np.percentile(v, 90)) if v else None
