"""Pallas TPU grouped matmul (megablocks-style, TPU-adapted).

GPU megablocks builds a block-sparse GEMM over ragged expert groups; the TPU
adaptation sorts tokens by expert and pads each group to the row-tile size so
every (tile_m × D) tile belongs to exactly one expert.  The per-tile expert
id arrives via *scalar prefetch* (SMEM) and drives the weight BlockSpec's
index_map — so each grid step DMAs exactly one expert's (D × F) panel into
VMEM and runs a dense MXU matmul.  No gather, no wasted flops on other
experts' weights.

Grid: (n_row_tiles, n_col_tiles).  F is tiled too so the weight panel
(D × tile_f) fits VMEM for large experts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import use_interpreter


def _gmm_kernel(t2e_ref, x_ref, w_ref, o_ref):
    del t2e_ref  # consumed by the index_map only
    x = x_ref[...].astype(jnp.float32)        # (tile_m, D)
    w = w_ref[0].astype(jnp.float32)          # (D, tile_f)
    o_ref[...] = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_m", "tile_f"))
def gmm(x: jax.Array, tile_expert: jax.Array, w: jax.Array, *,
        tile_m: int = 128, tile_f: int = 512) -> jax.Array:
    """x: (T, D) sorted+group-padded tokens (T % tile_m == 0);
    tile_expert: (T // tile_m,) int32 expert id per row tile;
    w: (E, D, F) with F % tile_f == 0.  Returns (T, F)."""
    T, D = x.shape
    E, _, F = w.shape
    assert T % tile_m == 0 and F % tile_f == 0, (T, tile_m, F, tile_f)
    grid = (T // tile_m, F // tile_f)
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tile_m, D), lambda i, j, t2e: (i, 0)),
                pl.BlockSpec((1, D, tile_f),
                             lambda i, j, t2e: (t2e[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((tile_m, tile_f),
                                   lambda i, j, t2e: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((T, F), x.dtype),
        interpret=use_interpreter(),
    )(tile_expert.astype(jnp.int32), x, w)
