"""Pipeline parallelism — the paper's Pipeline functional at cluster scale.

GPipe-style schedule via ``shard_map`` over a ``stage`` mesh axis: stage s
holds layers [s·L/S, (s+1)·L/S); microbatches stream through; the
stage-to-stage channel is ``ppermute`` — a synchronous, unbuffered,
point-to-point communication, i.e. *exactly* a CSP channel between Worker
processes (DESIGN.md mapping).  The bubble fraction is (S-1)/(M+S-1).

The implementation trades a little memory for simplicity: every stage
returns its output buffer and the caller reads the last stage's (out_specs
concatenate over the stage axis).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.stream import stack_microbatches

__all__ = ["pipeline_forward", "split_stages"]


def split_stages(stacked_params, n_stages: int):
    """(L, ...) layer-stacked params → (n_stages, L/S, ...)."""

    def _split(leaf):
        L = leaf.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return leaf.reshape(n_stages, L // n_stages, *leaf.shape[1:])

    return jax.tree_util.tree_map(_split, stacked_params)


def pipeline_forward(block_fn: Callable, stage_params, x, *, mesh,
                     n_stages: int, n_micro: int, stage_axis: str = "stage"):
    """Run ``x`` through all stages with a GPipe schedule.

    block_fn(local_params, h) -> h  applies one stage's layer stack
    stage_params: pytree with leading (n_stages, L/S, ...) — sharded P(stage)
    x: (B, S, D) with B % n_micro == 0.

    Returns (B, S, D), numerically identical to applying all layers in order.
    """
    B = x.shape[0]
    # the streaming runtime's microbatch schedule, reshaped for the mesh
    x_mb = stack_microbatches(x, n_micro)

    def staged(params_local, x_all):
        # params_local: (1, L/S, ...) this stage's layers; x_all replicated
        params_local = jax.tree_util.tree_map(lambda l: l[0], params_local)
        sid = jax.lax.axis_index(stage_axis)
        first = sid == 0
        last = sid == n_stages - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def step(t, carry):
            recv, out = carry
            m = t - sid  # microbatch index this stage works on
            m_c = jnp.clip(m, 0, n_micro - 1)
            x_in = jax.lax.dynamic_index_in_dim(x_all, m_c, 0,
                                                keepdims=False)
            h_in = jnp.where(first, x_in, recv)
            h_out = block_fn(params_local, h_in)
            # last stage: record its finished microbatch
            active = (m >= 0) & (m < n_micro)
            upd = jax.lax.dynamic_update_index_in_dim(out, h_out, m_c, 0)
            out = jnp.where(active & last, upd, out)
            # channel to the next stage (CSP rendezvous)
            recv_next = jax.lax.ppermute(h_out, stage_axis, perm)
            return recv_next, out

        # carries are stage-varying (ppermute/axis_index outputs): mark them
        out0 = jax.lax.pcast(jnp.zeros_like(x_all), (stage_axis,),
                             to="varying")
        recv0 = jax.lax.pcast(jnp.zeros_like(x_all[0]), (stage_axis,),
                              to="varying")
        _, out = jax.lax.fori_loop(0, n_micro + n_stages - 1, step,
                                   (recv0, out0))
        return out[None]  # (1, n_micro, mb, S, D) per stage

    spec_p = jax.tree_util.tree_map(lambda _: P(stage_axis), stage_params)
    out_all = jax.shard_map(
        staged, mesh=mesh,
        in_specs=(spec_p, P()),
        out_specs=P(stage_axis),
    )(stage_params, x_mb)
    return out_all[-1].reshape(B, *x.shape[1:])
