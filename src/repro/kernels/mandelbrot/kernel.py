"""Pallas TPU Mandelbrot kernel — the paper's flagship farm workload (§6.6).

The paper farms image *lines* over workers (their GPGPU note suggests
per-pixel parallelism).  The TPU-native blocking is a *tile* of 8 rows by at
most 512 columns per grid step: the escape iteration runs vectorised on the
VPU with a masked update, exactly the paper's escape-value semantics, and
``zr``, ``zi`` and the count stay in registers as loop values.

A pixel that has escaped is frozen by the masked update, so once no pixel of
a tile satisfies ``|z|^2 <= 4`` every later iteration is an exact identity on
that tile.  The kernel runs the ``iters % CHECK_EVERY`` odd steps first, then
blocks of ``CHECK_EVERY`` unrolled steps, and stops a tile at the first block
boundary at which none of its pixels is inside: the counts are those of all
``iters`` steps, and the work follows the tile's slowest pixel.  A second
output records, per tile, the iterations it ran.

The pixel coordinates come in as a row of ``cr`` and a column of ``ci``,
computed by the caller, so that they are bit-identical to a plain XLA
computation of the same expression.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import use_interpreter

TILE_H = 8          # rows of a tile: the TPU's sublane tiling
MAX_TILE_W = 512    # columns of a tile at most: four lane tiles
CHECK_EVERY = 64    # unrolled iterations between two tests of a tile
LANES = 128         # columns of a vector register


def tile_width(width: int) -> int:
    """The widest of 512, 256 and 128 columns that divides ``width``
    padded to a lane multiple."""
    padded = -(-width // LANES) * LANES
    return next(w for w in (MAX_TILE_W, 256, LANES) if padded % w == 0)


def _step(cr, ci, t, st):
    """Iteration ``t`` (from 0).  A pixel stays escaped once it has escaped
    (its z is frozen), so its count, the iterations at which it was inside,
    is one more than the last such ``t``."""
    zr, zi, n = st
    zr2, zi2 = zr * zr, zi * zi
    inside = (zr2 + zi2) <= 4.0
    return (jnp.where(inside, zr2 - zi2 + cr, zr),
            jnp.where(inside, 2.0 * zr * zi + ci, zi),
            jnp.where(inside, t + 1, n))


def _live(zr, zi):
    """Whether any pixel of the tile is still inside."""
    return jnp.min(zr * zr + zi * zi) <= 4.0


def _band_kernel(cr_ref, ci_ref, o_ref, ran_ref, *, iters: int):
    shape = o_ref.shape
    cr = jnp.broadcast_to(cr_ref[...], shape)
    ci = jnp.broadcast_to(ci_ref[...], shape)
    step = functools.partial(_step, cr, ci)
    # Mosaic takes a loop value's layout from its initial value and cannot
    # carry a constant-initialised array: start from a computed +0
    z0 = cr - cr
    # every pixel is inside at first (z = 0): the iters % CHECK_EVERY odd
    # steps run unchecked, then whole blocks while a pixel is inside
    rest = iters % CHECK_EVERY
    st = jax.lax.fori_loop(0, rest, step, (z0, z0, z0.astype(jnp.int32)),
                           unroll=True)

    def cond(c):
        k, zr, zi, _ = c
        return jnp.logical_and(k < iters, _live(zr, zi))

    def block(c):
        k, *st = c
        st = jax.lax.fori_loop(0, CHECK_EVERY, lambda j, s: step(k + j, s),
                               tuple(st), unroll=True)
        return (k + CHECK_EVERY, *st)

    k, _, _, n = jax.lax.while_loop(cond, block, (rest, *st))
    o_ref[...] = n
    ran_ref[...] = jnp.full(ran_ref.shape, k, jnp.int32)


@functools.partial(jax.jit, static_argnames=("iters",))
def band(cr: jax.Array, ci: jax.Array, *, iters: int):
    """Escape counts of the pixels ``cr[0, j] + i ci[r, 0]``.

    ``cr``: (1, W) float32 with W a multiple of :func:`tile_width`'s
    choice; ``ci``: (H, 1) float32 with H a multiple of ``TILE_H``.
    Returns the (H, W) int32 counts over ``iters`` steps and the
    (H // TILE_H, W // tile) int32 iterations each tile ran."""
    H, W = ci.shape[0], cr.shape[1]
    tw = tile_width(W)
    assert H % TILE_H == 0 and W % tw == 0, (H, W, tw)
    counts, ran = pl.pallas_call(
        functools.partial(_band_kernel, iters=iters),
        grid=(H // TILE_H, W // tw),
        in_specs=[pl.BlockSpec((1, tw), lambda i, j: (0, j)),
                  pl.BlockSpec((TILE_H, 1), lambda i, j: (i, 0))],
        out_specs=[pl.BlockSpec((TILE_H, tw), lambda i, j: (i, j)),
                   pl.BlockSpec((TILE_H, LANES), lambda i, j: (i, j))],
        out_shape=[jax.ShapeDtypeStruct((H, W), jnp.int32),
                   jax.ShapeDtypeStruct((H, W // tw * LANES), jnp.int32)],
        interpret=use_interpreter(),
    )(cr, ci)
    return counts, ran[::TILE_H, ::LANES]
