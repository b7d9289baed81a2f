"""Public grouped-matmul op: sort/pad tokens by expert, run the kernel,
unsort.  The contract mirrors what the MoE layer's ragged path needs."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .kernel import gmm as gmm_kernel


def sort_by_expert(x: jax.Array, expert_of: jax.Array, n_expert: int,
                   tile_m: int):
    """Sort tokens by expert and pad each group to a tile_m multiple.

    Returns (x_padded, tile_expert, inv_perm, valid_mask) where
    ``x_padded[perm_slot]`` ordering is recoverable via ``inv_perm``.
    """
    T = x.shape[0]
    order = jnp.argsort(expert_of, stable=True)
    sorted_e = expert_of[order]
    counts = jnp.bincount(expert_of, length=n_expert)
    padded_counts = ((counts + tile_m - 1) // tile_m) * tile_m
    cap = int(((T + tile_m - 1) // tile_m + n_expert) * tile_m)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(padded_counts)[:-1].astype(jnp.int32)])
    # position of sorted token t within its group:
    group_start_unpadded = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    pos_in_group = jnp.arange(T, dtype=jnp.int32) - group_start_unpadded[sorted_e]
    slot = starts[sorted_e] + pos_in_group  # destination row in padded buf
    x_p = jnp.zeros((cap,) + x.shape[1:], x.dtype).at[slot].set(x[order])
    valid = jnp.zeros((cap,), bool).at[slot].set(True)
    # expert owning each tile: from padded starts
    tile_ids = jnp.arange(cap // tile_m, dtype=jnp.int32)
    tile_row = tile_ids * tile_m
    tile_expert = jnp.searchsorted(jnp.cumsum(padded_counts), tile_row,
                                   side="right").astype(jnp.int32)
    tile_expert = jnp.clip(tile_expert, 0, n_expert - 1)
    inv = (order, slot)
    return x_p, tile_expert, inv, valid


def moe_apply(x: jax.Array, expert_of: jax.Array, w: jax.Array, *,
              tile_m: int = 128, tile_f: int = 512,
              use_pallas: bool = True) -> jax.Array:
    """Apply per-token expert matmul.  x: (T, D); w: (E, D, F) → (T, F)."""
    if not use_pallas:
        return ref.gmm(x, expert_of, w)
    E, D, F = w.shape
    tf = tile_f
    while F % tf:
        tf //= 2
    tf = max(tf, 1)
    x_p, tile_expert, (order, slot), _ = sort_by_expert(
        x, expert_of, E, tile_m)
    y_p = gmm_kernel(x_p, tile_expert, w, tile_m=tile_m, tile_f=tf)
    y_sorted = y_p[slot]  # back to sorted-token order
    T = x.shape[0]
    y = jnp.zeros((T, F), y_p.dtype).at[order].set(y_sorted)
    return y.astype(x.dtype)
