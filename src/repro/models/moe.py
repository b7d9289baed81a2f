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
over (batch × expert) mesh axes.

The other path is dropless and grouped (:func:`moe_apply_grouped`, taken
with ``moe_ragged`` or ``n_held``): token–expert pairs sorted by expert
through a grouped matmul (``lax.ragged_dot``, or the Pallas kernel of
kernels/moe_gmm where ``use_pallas``).  With ``n_held`` set the layer is one
chip's share of expert parallelism: it holds ``n_held`` experts, routes over
all ``n_experts`` and computes the part of the result its own experts give.
Both paths take their load-balancing loss from :func:`balance_loss`.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.parallel.axes import act
from . import layers

__all__ = ["moe_init", "moe_apply", "moe_apply_grouped", "capacity"]


def capacity(cfg_moe, seq_len: int) -> int:
    c = int(math.ceil(seq_len * cfg_moe.top_k / cfg_moe.n_experts
                      * cfg_moe.capacity_factor))
    return max(c, cfg_moe.top_k)


def moe_init(key, cfg, dtype) -> dict:
    m = cfg.moe
    D, E, F = cfg.d_model, m.n_experts, m.d_expert
    n = m.n_held or E
    ks = jax.random.split(key, 5)
    p = {
        "router": layers.dense_init(ks[0], (D, E), jnp.float32,
                                    scale=0.02),
        "experts": {
            "gate": _stack_init(ks[1], (n, D, F), dtype),
            "up": _stack_init(ks[2], (n, D, F), dtype),
            "down": _stack_init(ks[3], (n, F, D), dtype,
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
    the gates kept per token, the top-k choices (B,S,k).  Loop over the k
    choices, mesh-tf style."""
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
    return dispatch, combine, gates_sum, topi


def balance_loss(m, probs: jax.Array, topi: jax.Array) -> jax.Array:
    """The load-balancing loss of every path, per sequence then averaged.
    probs: (B, S, E); topi: (B, S, k); the counts carry no gradient.

    With ``m.seq_aux`` DeepSeek-V2's expert-level loss ``Σ_i f_i P_i``
    (``f_i = E/(k S) · #{t : i ∈ topk(t)}``, ``P_i`` the mean router
    probability of expert i); otherwise the switch-style ``E · Σ_e f_e ·
    p̄_e`` with ``f_e`` the share of tokens whose first choice is e."""
    B, S, E = probs.shape
    if m.seq_aux:
        counts = jnp.sum(jax.nn.one_hot(topi, E, dtype=jnp.float32),
                         axis=(1, 2))
        f = counts * (E / (m.top_k * S))
    else:
        f = E * jnp.mean(jax.nn.one_hot(topi[..., 0], E, dtype=jnp.float32),
                         axis=1)
    return jnp.mean(jnp.sum(f * jnp.mean(probs, axis=1), axis=-1))


# the grouped path groups at most this many tokens at once: its buffers
# hold every token-expert pair (dropless), k rows a token, so they are cut
# in chunks, each recomputed in the backward
GROUP_CHUNK = 4096


def _grouped_matmul(xs, w, sizes, cfg):
    """Rows of ``xs`` (T, D), sorted into groups of ``sizes`` rows, each
    through its own expert of ``w`` (n, D, F); rows past the groups come
    out as garbage the caller masks.  The Pallas kernel where the config
    asks for it (it has no backward), ``lax.ragged_dot`` otherwise."""
    if cfg.use_pallas:
        from repro.kernels.moe_gmm import ops as gmm_ops
        n = w.shape[0]
        group = jnp.searchsorted(jnp.cumsum(sizes), jnp.arange(xs.shape[0]),
                                 side="right")
        return gmm_ops.moe_apply(xs, jnp.minimum(group, n - 1), w,
                                 tile_m=128, use_pallas=True)
    return jax.lax.ragged_dot(xs, w, sizes)


def _grouped_part(p: dict, cfg, xf, topv, topi, first_expert: int):
    """One chunk of tokens through the held experts: xf (T, D); topv, topi
    (T, k) → ((T, D) float32, tokens routed to each held expert)."""
    m = cfg.moe
    k, n = m.top_k, m.n_held or m.n_experts
    T, D = xf.shape
    cd = xf.dtype
    local = topi.reshape(-1) - first_expert
    group = jnp.where((local >= 0) & (local < n), local, n)  # n: elsewhere
    order = jnp.argsort(group, stable=True)
    sizes = jnp.sum(jax.nn.one_hot(group, n, dtype=jnp.int32), axis=0)
    # rows past the groups belong to no expert: zero their inputs and
    # outputs (and so their cotangents) whatever the grouped matmul leaves
    valid = (jnp.arange(T * k) < jnp.sum(sizes))[:, None]
    xs = jnp.where(valid, xf[order // k], 0)
    w = p["experts"]
    g = _grouped_matmul(xs, w["gate"].astype(cd), sizes, cfg)
    u = _grouped_matmul(xs, w["up"].astype(cd), sizes, cfg)
    ys = _grouped_matmul(jax.nn.silu(g) * u, w["down"].astype(cd), sizes,
                         cfg)
    ys = jnp.where(valid, ys, 0)
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(T * k, dtype=order.dtype))
    y = jnp.einsum("tkd,tk->td", ys[inv].reshape(T, k, D), topv.astype(cd),
                   preferred_element_type=jnp.float32)
    return y, sizes


def moe_apply_grouped(p: dict, cfg, x: jax.Array, first_expert: int = 0):
    """The dropless grouped path: the part of the expert layer that experts
    ``first_expert .. first_expert + n - 1`` give, plus the shared experts,
    where ``n`` is ``n_held`` or, for a layer that holds every expert, all
    ``n_experts``.

    The router scores all ``n_experts`` and every token picks its top-k
    among them; the token–expert pairs that fall on a held expert are
    grouped by expert and run through a grouped matmul, dropless: the
    buffer holds every pair, those held elsewhere sorted past the groups.
    With every expert held it equals the capacity path where that one drops
    nothing (pinned by test).  x: (B, S, D) → (y, balance loss, tokens
    routed to each held expert (n_held,) int32, or None where the layer
    holds every expert)."""
    m = cfg.moe
    B, S, D = x.shape
    E, k = m.n_experts, m.top_k
    xf = x.reshape(-1, D)
    T = xf.shape[0]
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)  # (T, k)
    if m.router_norm_topk:
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    nc = next(c for c in range(1, T + 1)
              if T % c == 0 and T // c <= GROUP_CHUNK or c == T)
    part = jax.checkpoint(
        lambda a: _grouped_part(p, cfg, *a, first_expert=first_expert),
        prevent_cse=False)
    y, sizes = jax.lax.map(part, (xf.reshape(nc, T // nc, D),
                                  topv.reshape(nc, T // nc, k),
                                  topi.reshape(nc, T // nc, k)))
    y = y.reshape(B, S, D).astype(x.dtype)
    aux = balance_loss(m, probs.reshape(B, S, E), topi.reshape(B, S, k))
    if m.n_shared:
        y = y + layers.mlp(p["shared"], cfg, x, act_fn="swiglu")
    held = jnp.sum(sizes, axis=0) if m.n_held else None
    return act(y, "batch", "seq", "d"), aux, held


def moe_apply(p: dict, cfg, x: jax.Array):
    """x: (B, S, D) → (y, aux_loss, tokens routed to each held expert, or
    None where the layer holds every expert)."""
    if cfg.moe.n_held or cfg.moe_ragged:
        return moe_apply_grouped(p, cfg, x)
    m = cfg.moe
    B, S, D = x.shape
    E, k = m.n_experts, m.top_k
    C = capacity(m, S)
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    dispatch, combine, gates_sum, topi = _dispatch_combine(probs, k, C)
    aux = balance_loss(m, probs, topi)
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
    return act(y, "batch", "seq", "d"), aux, None
