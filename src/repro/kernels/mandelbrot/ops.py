"""Public Mandelbrot ops: coordinates, tile padding and cropping around the
band kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kernel

# the coordinate of padded rows and columns: outside the set, so a pad pixel
# escapes after one step and never keeps a tile iterating
_PAD = 4.0


@functools.partial(jax.jit, static_argnames=(
    "rows", "width", "x0", "y0", "pixel_delta", "max_iterations"))
def mandelbrot_band(row0, *, rows: int, width: int, x0: float = -2.25,
                    y0: float = -1.25, pixel_delta: float = 0.005,
                    max_iterations: int = 100):
    """Escape counts of the image rows ``row0 .. row0 + rows - 1``.

    ``row0`` is an absolute row index (an int32 scalar, traced, possibly
    negative), so the op batches under ``vmap``.  Pixel (r, j) is
    c = (x0 + delta * j) + i (y0 + delta * (row0 + r)) in float32.  Returns
    the (rows, width) int32 counts and the int32 iterations each kernel tile
    ran, one per tile of the padded band."""
    f32 = jnp.float32
    hp = -(-rows // kernel.TILE_H) * kernel.TILE_H
    wp = -(-width // kernel.LANES) * kernel.LANES
    delta = jnp.asarray(pixel_delta, f32)
    r = jnp.asarray(row0, jnp.int32) + jnp.arange(rows, dtype=jnp.int32)
    ci = jnp.asarray(y0, f32) + delta * r.astype(f32)
    cr = jnp.asarray(x0, f32) + delta * jnp.arange(width).astype(f32)
    ci = jnp.pad(ci, (0, hp - rows), constant_values=_PAD)
    cr = jnp.pad(cr, (0, wp - width), constant_values=_PAD)
    counts, ran = kernel.band(cr[None, :], ci[:, None],
                              iters=max_iterations)
    return counts[:rows, :width], ran


def mandelbrot(height: int, width: int, *, x0: float = -2.25,
               y0: float = -1.25, pixel_delta: float = 0.005,
               max_iterations: int = 100) -> jax.Array:
    """Escape counts of the whole (height, width) image: the band kernel
    at row 0."""
    counts, _ = mandelbrot_band(0, rows=height, width=width, x0=x0, y0=y0,
                                pixel_delta=pixel_delta,
                                max_iterations=max_iterations)
    return counts
