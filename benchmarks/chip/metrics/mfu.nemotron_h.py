"""The Nemotron-H training step's share of the chip's bf16 peak over the
traced stretch: the model operations of the steps wholly inside it
(``harness.nemotron_h_costs``: the forward, with the routed pairs on held
experts from the program's ``held_expert_tokens`` counter, and the backward
at twice the forward; recomputation left out) over the seconds from the
first one's dispatch to the last one's loss on the host, times the peak."""

import numpy as np

from harness import nemotron_h_costs


def read(r):
    steps = r.data.get("traced_steps")
    if r.peak is None or not steps or any(h is None for _, _, h in steps):
        return None
    B, S = r.data["batch_shape"]
    flops = sum(nemotron_h_costs.step_flops(r.cfg, B, S, int(np.sum(h)))
                for _, _, h in steps)
    seconds = steps[-1][1] - steps[0][0]
    return 100.0 * flops / (seconds * float(r.peak["bf16_flops_per_s"]))
