"""Plain reference of the Mandelbrot escape-time count (arXiv:2103.12031 §6.6).

For pixel (row, col): c = (x0 + delta*col) + i (y0 + delta*row); z starts
at 0; for each of ``iters`` steps, while |z|^2 <= 4, z <- z^2 + c and the
count goes up by one.  Arithmetic in ``dtype`` (float32 as the configuration
states; ``bfloat16`` is the control).  Rows may lie outside the image: a
batch's rows are its top rows plus the row within the band.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def counts(rows, width: int, x0: float, y0: float, delta: float,
           iters: int, dtype: str = "float32"):
    """``rows``: (n,) int32 absolute row indices -> (n, width) int32."""
    dt = jnp.dtype(dtype)
    ci = (jnp.asarray(y0, dt) + jnp.asarray(delta, dt)
          * rows.astype(dt))[:, None]
    cr = (jnp.asarray(x0, dt) + jnp.asarray(delta, dt)
          * jnp.arange(width).astype(dt))[None, :]
    cr = jnp.broadcast_to(cr, (rows.shape[0], width))
    ci = jnp.broadcast_to(ci, (rows.shape[0], width))

    def body(_, st):
        zr, zi, n = st
        zr2, zi2 = zr * zr, zi * zi
        inside = (zr2 + zi2) <= 4.0
        return (jnp.where(inside, zr2 - zi2 + cr, zr),
                jnp.where(inside, 2 * zr * zi + ci, zi),
                n + inside.astype(jnp.int32))

    z = jnp.zeros_like(cr)
    _, _, n = jax.lax.fori_loop(0, iters, body,
                                (z, z, jnp.zeros(cr.shape, jnp.int32)))
    return n
