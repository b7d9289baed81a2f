"""Public SSD op: head folding, decay precompute, Pallas/jnp dispatch."""

from __future__ import annotations

import jax.numpy as jnp

from . import ref
from .kernel import ssd_scan


def ssd(x, dt, A, B, C, *, chunk: int = 64, use_pallas: bool = False,
        return_state: bool = False):
    """Multi-head SSD.

    x: (batch, S, H, P); dt: (batch, S, H); A: (H,);
    B, C: (batch, S, G, N) with G | H: head h reads state group h // (H/G).
    Returns y: (batch, S, H, P); with ``return_state`` also the final state
    (batch, H, N, P) (jnp path — used by prefill).
    """
    b, S, H, P = x.shape
    ch = chunk if S % chunk == 0 else S
    if not use_pallas or return_state:
        y, hT = ref.ssd_chunked(x, dt, A, B, C, chunk=ch)
        return (y, hT) if return_state else y
    # the kernel takes one sequence per (batch, head), its groups repeated
    rep = H // B.shape[2]

    def fold(a):
        return jnp.moveaxis(a, 2, 1).reshape((b * H, S) + a.shape[3:])

    xf, dtf = fold(x), fold(dt)
    Bf, Cf = (fold(jnp.repeat(m, rep, axis=2)) for m in (B, C))
    a_log = dtf * jnp.tile(A, b)[:, None]  # head h of every batch row
    y = ssd_scan(xf, dtf, a_log, Bf, Cf, chunk=ch)
    return jnp.moveaxis(y.reshape(b, H, S, P), 1, 2)
