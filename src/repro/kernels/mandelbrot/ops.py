"""Public Mandelbrot op: tile alignment + Pallas/jnp dispatch."""

from __future__ import annotations

import jax

from . import ref
from .kernel import mandelbrot as mandelbrot_pallas


def mandelbrot(height: int, width: int, *, x0: float = -2.25,
               y0: float = -1.25, pixel_delta: float = 0.005,
               max_iterations: int = 100, tile_h: int = 8,
               use_pallas: bool = True) -> jax.Array:
    if not use_pallas:
        return ref.mandelbrot(height, width, x0=x0, y0=y0,
                              pixel_delta=pixel_delta,
                              max_iterations=max_iterations)
    # row tiles are whole multiples of the TPU's 8-row sublane tiling; the
    # height is padded up to a tile multiple and the extra rows cropped
    th = max(8, tile_h // 8 * 8)
    padded = -(-height // th) * th
    out = mandelbrot_pallas(height=padded, width=width, x0=x0, y0=y0,
                            pixel_delta=pixel_delta,
                            max_iterations=max_iterations, tile_h=th)
    return out[:height]
