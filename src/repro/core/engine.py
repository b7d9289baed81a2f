"""Engines (paper §5.4): shared-data iterative and stencil process engines.

``MultiCoreEngine`` (paper §6.2 Jacobi, §6.3 N-body): a root + N worker nodes
iterate over a shared matrix; workers each update their own partition while
reading everything, a barrier separates iterations, and the root runs a
sequential error/update phase.

TPU adaptation: the partitioned compute phase is a ``shard_map`` over a mesh
axis (out_specs concatenate the partitions — the barrier *is* the collective);
the root's sequential phase is the unsharded epilogue of the loop body.  On a
single device the engine runs the same partition loop unrolled, which keeps
the sequential oracle bit-identical to the parallel form.

``StencilEngine`` (paper §6.4): one image-processing stage; chains of engines
form the paper's Listing 17 network.  The convolution hotspot is backed by
the Pallas stencil kernel (kernels/stencil) with a pure-jnp fallback; with a
mesh, rows are block-sharded and halos exchanged with ``ppermute``.

User methods stay sequential-style (paper P4): ``partition`` slices state with
:func:`rows` (which works under both static and traced offsets), ``calculation``
maps a partition to its update, ``update``/``error`` are plain array code.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .dataflow import Kind, ProcessDef

__all__ = ["rows", "IterativeEngine", "Stencil", "MultiCoreEngine",
           "StencilEngine"]


def rows(x: jax.Array, lo, size: int) -> jax.Array:
    """Slice ``size`` rows starting at ``lo`` (static int or traced scalar)."""
    return jax.lax.dynamic_slice_in_dim(x, lo, size, axis=0)


@dataclasses.dataclass
class IterativeEngine:
    """BSP iteration over partitioned shared state.

    partition(state, lo, size) -> part        (read anything, slice own rows)
    calculation(part) -> update rows (size, ...)
    update(state, full_update) -> state       (root sequential phase)
    error(state, full_update) -> residual     (optional; enables tol loop)
    """

    partition: Callable
    calculation: Callable
    update: Callable
    n_rows: int
    nodes: int = 1
    error: Optional[Callable] = None
    iterations: Optional[int] = None
    tol: Optional[float] = None
    max_iterations: int = 10_000
    axis: Optional[str] = None  # mesh axis for the partitioned phase

    def __post_init__(self) -> None:
        if (self.iterations is None) == (self.tol is None):
            raise ValueError("specify exactly one of iterations= or tol=")

    # -- one BSP superstep: partitioned calc + root epilogue -------------
    def _full_update(self, state, mesh):
        n, k = self.n_rows, self.nodes
        if n % k:
            raise ValueError(f"n_rows={n} not divisible by nodes={k}")
        size = n // k
        if mesh is not None and self.axis is not None:
            axis = self.axis

            def shard_calc(st):
                idx = jax.lax.axis_index(axis)
                part = self.partition(st, idx * size, size)
                return self.calculation(part)

            spec_in = jax.tree_util.tree_map(lambda _: P(), state)
            upd = jax.shard_map(
                shard_calc, mesh=mesh,
                in_specs=(spec_in,), out_specs=P(axis),
            )(state)
            return upd
        parts = [self.calculation(self.partition(state, i * size, size))
                 for i in range(k)]
        return jnp.concatenate(parts, axis=0) if k > 1 else parts[0]

    def apply(self, state, mesh=None):
        if self.iterations is not None:
            def body(_, st):
                upd = self._full_update(st, mesh)
                return self.update(st, upd)

            return jax.lax.fori_loop(0, self.iterations, body, state)

        # tolerance loop (paper's Jacobi): root checks the error each sweep
        def cond(carry):
            st, err, it = carry
            return jnp.logical_and(err > self.tol, it < self.max_iterations)

        def body(carry):
            st, _, it = carry
            upd = self._full_update(st, mesh)
            err = self.error(st, upd)
            return self.update(st, upd), err, it + 1

        init = (state, jnp.asarray(jnp.inf, jnp.float32), jnp.asarray(0))
        final, _, _ = jax.lax.while_loop(cond, body, init)
        return final

    def as_worker_fn(self):
        return lambda item, *_: self.apply(item)


# --------------------------------------------------------------------------
# Stencil engine
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Stencil:
    """One image-processing stage: either an elementwise ``op`` (e.g.
    greyscale) or a ``kernel`` convolution (paper Listing 17 engines)."""

    kernel: Optional[jax.Array] = None
    op: Optional[Callable] = None
    axis: Optional[str] = None
    nodes: int = 1
    use_pallas: bool = False  # Pallas kernel vs pure jnp

    def __post_init__(self) -> None:
        if (self.kernel is None) == (self.op is None):
            raise ValueError("specify exactly one of kernel= or op=")

    def _conv_local(self, img: jax.Array) -> jax.Array:
        if self.use_pallas:
            from repro.kernels.stencil import ops as stencil_ops
            return stencil_ops.stencil2d(img, self.kernel)
        from repro.kernels.stencil import ref as stencil_ref
        return stencil_ref.stencil2d(img, self.kernel)

    def apply(self, img, mesh=None):
        if self.op is not None:
            return self.op(img)
        k = self.kernel
        halo = k.shape[0] // 2
        if mesh is None or self.axis is None:
            return self._conv_local(img)
        axis = self.axis

        def shard_conv(tile):
            # exchange halo rows with mesh neighbours (zero pad at edges)
            up = jax.lax.ppermute(
                tile[-halo:], axis,
                [(i, i + 1) for i in range(self.nodes - 1)])
            down = jax.lax.ppermute(
                tile[:halo], axis,
                [(i + 1, i) for i in range(self.nodes - 1)])
            padded = jnp.concatenate([up, tile, down], axis=0)
            out = self._conv_local(padded)
            return out[halo:-halo] if halo else out

        return jax.shard_map(
            shard_conv, mesh=mesh, in_specs=(P(axis),), out_specs=P(axis),
        )(img)

    def as_worker_fn(self):
        return lambda item, *_: self.apply(item)


# --------------------------------------------------------------------------
# ProcessDef factories with the paper's names
# --------------------------------------------------------------------------

def MultiCoreEngine(
    *,
    nodes: int,
    n_rows: int,
    partitionMethod: Callable,
    calculationMethod: Callable,
    updateMethod: Callable,
    errorMethod: Optional[Callable] = None,
    iterations: Optional[int] = None,
    tol: Optional[float] = None,
    axis: Optional[str] = None,
    name: str = "mcEngine",
) -> ProcessDef:
    """Paper Listing 15/16 signature (camelCase kept deliberately)."""
    eng = IterativeEngine(
        partition=partitionMethod,
        calculation=calculationMethod,
        update=updateMethod,
        error=errorMethod,
        n_rows=n_rows,
        nodes=nodes,
        iterations=iterations,
        tol=tol,
        axis=axis,
    )
    return ProcessDef(name=name, kind=Kind.ENGINE, engine=eng)


def StencilEngine(
    *,
    nodes: int = 1,
    convolutionData: Optional[jax.Array] = None,
    functionMethod: Optional[Callable] = None,
    axis: Optional[str] = None,
    use_pallas: bool = False,
    name: str = "stencilEngine",
) -> ProcessDef:
    """Paper Listing 17 signature: kernel convolution or pixel function."""
    eng = Stencil(kernel=convolutionData, op=functionMethod, axis=axis,
                  nodes=nodes, use_pallas=use_pallas)
    return ProcessDef(name=name, kind=Kind.ENGINE, engine=eng)
