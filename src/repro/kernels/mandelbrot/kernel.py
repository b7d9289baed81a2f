"""Pallas TPU Mandelbrot kernel — the paper's flagship farm workload (§6.6).

The paper farms image *lines* over workers (their GPGPU note suggests
per-pixel parallelism).  The TPU-native blocking is a row *tile* per grid
step: each program materialises its (tile_h × W) coordinate block from
``program_id`` with iota (no input stream at all — a pure Emit-less
generator kernel) and runs the escape iteration vectorised on the VPU with a
masked update, exactly the paper's escape-value semantics.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import use_interpreter


def _mandelbrot_kernel(o_ref, zr_ref, zi_ref, *, x0: float, y0: float,
                       delta: float, max_iterations: int, tile_h: int,
                       width: int):
    i = pl.program_id(0)
    # Mosaic's iota is integer-only: build pixel indices in int32, then cast
    r = jax.lax.broadcasted_iota(jnp.int32, (tile_h, width), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (tile_h, width), 1)
    ci = y0 + delta * (i * tile_h + r).astype(jnp.float32)
    cr = x0 + delta * c.astype(jnp.float32)

    # the iteration state lives in VMEM refs (z in scratch, the count in the
    # output block): Mosaic cannot carry a constant-initialised array through
    # a loop, whose layout it takes from the (replicated) initial value
    zr_ref[...] = jnp.zeros((tile_h, width), jnp.float32)
    zi_ref[...] = jnp.zeros((tile_h, width), jnp.float32)
    o_ref[...] = jnp.zeros((tile_h, width), jnp.int32)

    def body(_, carry):
        zr, zi = zr_ref[...], zi_ref[...]
        zr2, zi2 = zr * zr, zi * zi
        inside = (zr2 + zi2) <= 4.0
        zr_ref[...] = jnp.where(inside, zr2 - zi2 + cr, zr)
        zi_ref[...] = jnp.where(inside, 2.0 * zr * zi + ci, zi)
        o_ref[...] += inside.astype(jnp.int32)
        return carry

    jax.lax.fori_loop(0, max_iterations, body, 0)


@functools.partial(jax.jit, static_argnames=(
    "height", "width", "x0", "y0", "pixel_delta", "max_iterations", "tile_h"))
def mandelbrot(*, height: int, width: int, x0: float = -2.25,
               y0: float = -1.25, pixel_delta: float = 0.005,
               max_iterations: int = 100, tile_h: int = 8) -> jax.Array:
    assert height % tile_h == 0, (height, tile_h)
    kern = functools.partial(
        _mandelbrot_kernel, x0=x0, y0=y0, delta=pixel_delta,
        max_iterations=max_iterations, tile_h=tile_h, width=width)
    return pl.pallas_call(
        kern,
        grid=(height // tile_h,),
        out_specs=pl.BlockSpec((tile_h, width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((height, width), jnp.int32),
        scratch_shapes=[pltpu.VMEM((tile_h, width), jnp.float32)] * 2,
        interpret=use_interpreter(),
    )()
