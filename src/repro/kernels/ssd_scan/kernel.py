"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

TPU adaptation: the Mamba2 paper's Triton kernel parallelises chunks across
thread-blocks and carries states through global memory between kernel
launches.  On TPU the sequential-innermost-grid-axis property gives the
inter-chunk recurrence for free: grid = (BH, S/chunk) with the running state
``h`` (N × P, f32) living in VMEM scratch across the chunk axis — one kernel,
no HBM round-trip for the state, and the three chunk matmuls
(C·Bᵀ "attention", W·x, and the state update Bᵀ·(w⊙x)) all hit the MXU.

Log-decays are pre-computed by ops.py as ``a = dt * A_head`` (≤ 0), so all
exponentials are of non-positive numbers — numerically safe by construction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import use_interpreter


def _dot(a, b, contract):
    """f32 matmul on the MXU at full f32 precision: Mosaic's default is one
    bf16 pass, about 1e-2 off the f32 recurrence at mamba2-2.7b widths.  The
    prefix sums ride it too (Mosaic has no cumsum, no transpose of a column,
    no scalar broadcast)."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, h_ref, *,
                chunk: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)   # (L, P)
    dt = dt_ref[0].astype(jnp.float32)  # (L, 1)
    a = a_ref[0].astype(jnp.float32)   # (L, 1) log-decays (≤ 0)
    B = b_ref[0].astype(jnp.float32)   # (L, N)
    C = c_ref[0].astype(jnp.float32)   # (L, N)
    L = chunk

    ti = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = si <= ti
    col = ((1,), (0,))  # plain matmul: (L, L) @ (L, 1) -> a column
    row = ((0,), (0,))  # contract leading dims: (L, 1)ᵀ @ (L, M) -> a row
    cum = _dot(causal.astype(jnp.float32), a, col)          # (L, 1) inclusive
    cum_row = _dot(a, (ti <= si).astype(jnp.float32), row)  # (1, L)
    dt_row = _dot(dt, (ti == si).astype(jnp.float32), row)  # (1, L)
    rest = _dot((si > ti).astype(jnp.float32), a, col)      # (L, 1) total-cum
    total = _dot(a, jnp.ones((L, x.shape[1]), jnp.float32), row)  # (1, P)

    # intra-chunk (the "duality" attention form)
    G = _dot(C, B, ((1,), (1,)))  # (L, L)
    decay = jnp.exp(cum - cum_row)
    W = jnp.where(causal, G * decay, 0.0) * dt_row
    y = _dot(W, x, col)  # (L, P)

    # inter-chunk contribution from the carried state
    h = h_ref[...]  # (N, P)
    Cw = C * jnp.exp(cum)
    y = y + _dot(Cw, h, col)

    # state update: h' = exp(total) h + Bᵀ (w ⊙ x)
    Bw = B * (jnp.exp(rest) * dt)
    h_ref[...] = jnp.exp(total) * h + _dot(Bw, x, row)

    y_ref[0, ...] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, B: jax.Array,
             C: jax.Array, *, chunk: int = 128) -> jax.Array:
    """x: (BH, S, P); dt, a: (BH, S); B, C: (BH, S, N).  S % chunk == 0.

    ``a`` are per-step log-decays (dt * A_head, ≤ 0).  Returns y: (BH, S, P).
    """
    BH, S, P = x.shape
    N = B.shape[-1]
    assert S % chunk == 0, (S, chunk)
    grid = (BH, S // chunk)
    kern = functools.partial(_ssd_kernel, chunk=chunk)
    # dt and a ride as (BH, S, 1) columns: a (1, chunk, 1) block meets the
    # (8, 128) tiling rule, which a (1, chunk) block of a 2-D array does not
    col = pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            col,
            col,
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=use_interpreter(),
    )(x, dt[..., None], a[..., None], B, C)
