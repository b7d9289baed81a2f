"""Public stencil op: picks the row tile, pads to it, dispatches Pallas vs ref."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .kernel import stencil2d_pallas, taps_of

# VMEM the kernel's pipelined blocks may hold: three input blocks and one
# output block, each double-buffered.  A quarter of the v5e's 16 MiB scoped
# default, which leaves room for the in-kernel halo tile and accumulator.
VMEM_BLOCK_BUDGET = 4 << 20
_BUFFERED_BLOCKS = 4 * 2


def tile_rows(H: int, W: int, itemsize: int, tile_h: int = 128) -> int:
    """Row tile for an (H, W) image: at most ``tile_h`` rows, within
    :data:`VMEM_BLOCK_BUDGET`, a multiple of the 8-row sublane tiling, and
    no taller than H rounded up to 8."""
    fit = VMEM_BLOCK_BUDGET // (W * itemsize * _BUFFERED_BLOCKS)
    th = max(8, min(tile_h, fit) // 8 * 8)
    return min(th, -(-H // 8) * 8)


def stencil2d(img: jax.Array, kernel: jax.Array, *, tile_h: int = 128,
              use_pallas: bool = True) -> jax.Array:
    """2D same-padding stencil. Pallas path pads H to a tile multiple."""
    if not use_pallas:
        return ref.stencil2d(img, kernel)
    H, W = img.shape
    taps = taps_of(kernel)
    th = tile_rows(H, W, img.dtype.itemsize, tile_h)
    if H % th:
        img = jnp.pad(img, ((0, th - H % th), (0, 0)))
    # zero row padding bleeds at most `halo` rows past H; the crop restores
    # same-padding semantics exactly because ref also zero-pads
    return stencil2d_pallas(img, taps=taps, tile_h=th)[:H]
