"""Mixture-of-Experts FFN — capacity-based dispatch (shardable everywhere).

This *is* the paper's farm, one level down: the router is ``OneFanAny``
(tokens to any free expert up to capacity), experts are the Worker group,
the combine is ``AnyFanOne`` weighted by the router gate.  The assigned MoE
archs exercise both flavours: phi3.5-moe (16 coarse experts, top-2) and
deepseek-moe-16b (64 fine-grained + 2 shared experts, top-6, normalised
gates).

Dispatch follows the mesh-tf/MaxText "grouped capacity" scheme: each batch
row is a group with capacity C = ceil(S · k / E · cf); dispatch/combine are
(B, S, E, C) one-hots contracted with einsums — every tensor is shardable
over (batch × expert) mesh axes, which is what makes the 16×16 dry-run
tractable.  The ragged grouped-matmul path (kernels/moe_gmm) is the
beyond-paper optimisation lever.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.parallel.axes import act
from . import layers

__all__ = ["moe_init", "moe_apply", "capacity"]


def capacity(cfg_moe, seq_len: int) -> int:
    c = int(math.ceil(seq_len * cfg_moe.top_k / cfg_moe.n_experts
                      * cfg_moe.capacity_factor))
    return max(c, cfg_moe.top_k)


def moe_init(key, cfg, dtype) -> dict:
    m = cfg.moe
    D, E, F = cfg.d_model, m.n_experts, m.d_expert
    ks = jax.random.split(key, 5)
    p = {
        "router": layers.dense_init(ks[0], (D, E), jnp.float32,
                                    scale=0.02),
        "experts": {
            "gate": _stack_init(ks[1], (E, D, F), dtype),
            "up": _stack_init(ks[2], (E, D, F), dtype),
            "down": _stack_init(ks[3], (E, F, D), dtype,
                                scale=1.0 / math.sqrt(F)),
        },
    }
    if m.n_shared:
        p["shared"] = layers.mlp_init(ks[4], cfg, dtype,
                                      d_ff=m.n_shared * F)
    return p


def _stack_init(key, shape, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(shape[1])
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def _dispatch_combine(probs: jax.Array, k: int, C: int):
    """probs: (B, S, E) f32 → dispatch (B,S,E,C) bool-ish, combine f32,
    aux load-balancing loss.  Loop over the k choices, mesh-tf style."""
    B, S, E = probs.shape
    cdtype = probs.dtype
    dispatch = jnp.zeros((B, S, E, C), cdtype)
    combine = jnp.zeros((B, S, E, C), cdtype)
    count_e = jnp.zeros((B, E), cdtype)  # already-assigned per expert
    gates_sum = jnp.zeros((B, S), cdtype)
    topv, topi = jax.lax.top_k(probs, k)  # (B,S,k)
    for choice in range(k):
        g = topv[..., choice]
        e_onehot = jax.nn.one_hot(topi[..., choice], E, dtype=cdtype)
        # position of each token within its expert's capacity buffer
        pos = jnp.cumsum(e_onehot, axis=1) - e_onehot + count_e[:, None, :]
        pos_tok = jnp.sum(pos * e_onehot, axis=-1)  # (B,S)
        keep = pos_tok < C
        pos_onehot = jax.nn.one_hot(pos_tok.astype(jnp.int32), C,
                                    dtype=cdtype)
        slot = (e_onehot[..., None] * pos_onehot[:, :, None, :]
                * keep[..., None, None].astype(cdtype))
        dispatch = dispatch + slot
        combine = combine + slot * g[..., None, None]
        count_e = count_e + jnp.sum(
            e_onehot * keep[..., None].astype(cdtype), axis=1)
        gates_sum = gates_sum + g * keep.astype(cdtype)
    # aux loss (switch-style): E · Σ_e f_e · p̄_e, per group then averaged
    frac_tokens = jnp.mean(
        jax.nn.one_hot(topi[..., 0], E, dtype=cdtype), axis=1)  # (B,E)
    mean_probs = jnp.mean(probs, axis=1)
    aux = E * jnp.mean(jnp.sum(frac_tokens * mean_probs, axis=-1))
    return dispatch, combine, gates_sum, aux


def moe_apply_ragged(p: dict, cfg, x: jax.Array):
    """Ragged (capacity-free) MoE via the grouped-matmul kernel: tokens are
    sorted by expert and each group runs a dense MXU matmul — O(T·top_k)
    work instead of O(E·C) padded streams (the §Perf "real next step" for
    the MoE cell).  Exactly equal to the capacity path when that path is
    dropless (pinned by test)."""
    from repro.kernels.moe_gmm import ops as gmm_ops

    m = cfg.moe
    B, S, D = x.shape
    E, k = m.n_experts, m.top_k
    cd = x.dtype
    xf = x.reshape(-1, D)
    T = xf.shape[0]
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)  # (T, k)
    if m.router_norm_topk:
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    xs_rep = jnp.repeat(xf, k, axis=0)  # (T·k, D): one row per choice
    eo = topi.reshape(-1)
    w = p["experts"]
    kw = dict(tile_m=128, use_pallas=cfg.use_pallas)
    g = gmm_ops.moe_apply(xs_rep, eo, w["gate"].astype(cd), **kw)
    u = gmm_ops.moe_apply(xs_rep, eo, w["up"].astype(cd), **kw)
    h = jax.nn.silu(g) * u
    yd = gmm_ops.moe_apply(h, eo, w["down"].astype(cd), **kw)
    y = jnp.sum(yd.reshape(T, k, D)
                * topv[..., None].astype(yd.dtype), axis=1)
    y = y.reshape(B, S, D).astype(cd)
    frac = jnp.mean(jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(frac * jnp.mean(probs, axis=0))
    if m.n_shared:
        y = y + layers.mlp(p["shared"], cfg, x, act_fn="swiglu")
    return act(y, "batch", "seq", "d"), aux


def moe_apply(p: dict, cfg, x: jax.Array):
    """x: (B, S, D) → (y, aux_loss)."""
    if cfg.moe_ragged:
        return moe_apply_ragged(p, cfg, x)
    m = cfg.moe
    B, S, D = x.shape
    E, k = m.n_experts, m.top_k
    C = capacity(m, S)
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    dispatch, combine, gates_sum, aux = _dispatch_combine(probs, k, C)
    if m.router_norm_topk:
        combine = combine / jnp.maximum(
            gates_sum[..., None, None], 1e-9)
    cd = x.dtype
    dispatch = act(dispatch.astype(cd), "batch", "seq", "expert", None)
    combine = act(combine.astype(jnp.float32), "batch", "seq", "expert", None)
    # gather expert inputs: (E, B, C, D)
    xe = jnp.einsum("bsec,bsd->ebcd", dispatch, x)
    xe = act(xe, "expert", "batch", None, "d")
    w = p["experts"]
    g = jnp.einsum("ebcd,edf->ebcf", xe, w["gate"].astype(cd))
    u = jnp.einsum("ebcd,edf->ebcf", xe, w["up"].astype(cd))
    h = jax.nn.silu(g) * u
    h = act(h, "expert", "batch", None, "ff")
    ye = jnp.einsum("ebcf,efd->ebcd", h, w["down"].astype(cd))
    ye = act(ye, "expert", "batch", None, "d")
    y = jnp.einsum("bsec,ebcd->bsd", combine.astype(cd), ye)
    if m.n_shared:
        y = y + layers.mlp(p["shared"], cfg, x, act_fn="swiglu")
    return act(y, "batch", "seq", "d"), aux
