"""Public SSD op: head folding, decay precompute, Pallas/jnp dispatch."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .kernel import ssd_scan


def ssd(x, dt, A, B, C, *, chunk: int = 64, use_pallas: bool = False,
        return_state: bool = False):
    """Multi-head SSD.

    x: (batch, S, H, P); dt: (batch, S, H); A: (H,);
    B, C: (batch, S, G, N) with G ∈ {1, H} (state groups broadcast to heads).
    Returns y: (batch, S, H, P); with ``return_state`` also the final state
    (batch·H, N, P) (jnp path — used by prefill).
    """
    b, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    xf = jnp.moveaxis(x, 2, 1).reshape(b * H, S, P)
    dtf = jnp.moveaxis(dt, 2, 1).reshape(b * H, S)
    if G == 1:
        Bf = jnp.broadcast_to(B, (b, S, H, N))
        Cf = jnp.broadcast_to(C, (b, S, H, N))
    else:
        Bf, Cf = B, C
    Bf = jnp.moveaxis(Bf, 2, 1).reshape(b * H, S, N)
    Cf = jnp.moveaxis(Cf, 2, 1).reshape(b * H, S, N)
    Af = jnp.tile(A, b)  # (b*H,) — head h of every batch row
    ch = chunk if S % chunk == 0 else S
    if use_pallas and not return_state:
        a_log = dtf * Af[:, None]
        y = ssd_scan(xf, dtf, a_log, Bf, Cf, chunk=ch)
        hT = None
    else:
        y, hT = ref.ssd_chunked(xf, dtf, Af, Bf, Cf, chunk=ch)
    out = jnp.moveaxis(y.reshape(b, H, S, P), 1, 2)
    if return_state:
        return out, hT
    return out
