"""Pallas TPU stencil kernel (paper §6.4's compute hotspot, TPU-adapted).

GPU stencils tile into shared memory per thread-block; the TPU adaptation
tiles *rows* into VMEM blocks streamed from HBM, with the row-halo obtained
by passing the image three times with shifted block index maps (previous /
current / next row block) — no gather, no unaligned loads, VPU-friendly
shifted-slice accumulation over the kernel taps.

Grid: one program per row tile.  Each program sees
  prev  (TH, W)  row block i-1 (clamped at 0; masked off when i == 0)
  cur   (TH, W)  row block i
  next  (TH, W)  row block i+1 (clamped; masked off when i == last)
and writes ``out`` (TH, W).  Column halo is materialised in-register by
zero-padding the assembled (TH + 2h, W) tile to (TH + 2h, W + 2h).

The kernel taps are compile-time constants (closed over), so the loop over
taps unrolls into 2·k² fused multiply-adds on the VPU — the MXU is not used
(stencils are memory-bound; see EXPERIMENTS.md roofline for T6).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import use_interpreter


def _stencil_kernel(prev_ref, cur_ref, next_ref, out_ref, *, taps: tuple,
                    halo: int, tile_h: int):
    i = pl.program_id(0)
    n = pl.num_programs(0)
    prev = prev_ref[...]
    cur = cur_ref[...]
    nxt = next_ref[...]
    acc_dtype = jnp.float32
    # halo rows, zeroed at the image edges
    top = jnp.where(i > 0, prev[-halo:, :], jnp.zeros_like(prev[-halo:, :]))
    bot = jnp.where(i < n - 1, nxt[:halo, :], jnp.zeros_like(nxt[:halo, :]))
    tile = jnp.concatenate([top, cur, bot], axis=0).astype(acc_dtype)
    # column halo via zero pad (in-VMEM)
    tile = jnp.pad(tile, ((0, 0), (halo, halo)))
    W = cur.shape[1]
    out = jnp.zeros((tile_h, W), acc_dtype)
    for dr in range(2 * halo + 1):
        for dc in range(2 * halo + 1):
            w = taps[dr][dc]
            if w == 0.0:
                continue
            out = out + w * tile[dr:dr + tile_h, dc:dc + W]
    out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("taps", "tile_h"))
def stencil2d_pallas(img: jax.Array, *, taps: tuple,
                     tile_h: int = 128) -> jax.Array:
    """``img`` (H, W) with H % tile_h == 0; ``taps`` a tuple-of-tuples kernel."""
    H, W = img.shape
    k = len(taps)
    halo = k // 2
    assert H % tile_h == 0, f"H={H} must be divisible by tile_h={tile_h}"
    assert tile_h >= halo, "tile must cover the halo"
    n_tiles = H // tile_h
    grid = (n_tiles,)
    bs = pl.BlockSpec((tile_h, W), lambda i: (i, 0))
    bs_prev = pl.BlockSpec((tile_h, W), lambda i: (jnp.maximum(i - 1, 0), 0))
    bs_next = pl.BlockSpec(
        (tile_h, W), lambda i: (jnp.minimum(i + 1, n_tiles - 1), 0))
    kern = functools.partial(_stencil_kernel, taps=taps, halo=halo,
                             tile_h=tile_h)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[bs_prev, bs, bs_next],
        out_specs=bs,
        out_shape=jax.ShapeDtypeStruct((H, W), img.dtype),
        interpret=use_interpreter(),
    )(img, img, img)


def taps_of(kernel_array) -> tuple:
    """Convert a (k,k) array kernel to the hashable compile-time form."""
    a = np.asarray(kernel_array, dtype=np.float32)
    return tuple(tuple(float(x) for x in row) for row in a)
