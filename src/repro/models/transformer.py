"""Unified decoder LM covering the dense / moe / ssm / hybrid / vlm families.

A model is a sequence of *segments* — homogeneous runs of one block kind that
are parameter-stacked and executed with ``lax.scan`` (small HLO, fast
compile, remat-friendly), mirroring MaxText.  zamba2's *shared* attention
block (one parameter set applied every ``period`` layers) sits between
mamba segments; its weights live once in the param tree.  A config with a
``pattern`` (Nemotron-H) has one mixer per layer, each block
``x + mixer(norm(x))``: its segments are the pattern's runs.

Entry points::

    init_params(cfg, key)                       -> params
    forward(cfg, params, tokens, ...)           -> (logits, aux)
    loss_fn(cfg, params, batch)                 -> (loss, metrics)
    init_cache(cfg, batch, max_len)             -> cache
    decode_step(cfg, params, cache, tokens)     -> (logits, cache)
    prefill(cfg, params, tokens, max_len)       -> (logits, cache)
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.parallel.axes import act
from . import layers, mamba, moe

__all__ = ["structure", "init_params", "forward", "loss_fn", "init_cache",
           "decode_step", "prefill", "param_count"]


# --------------------------------------------------------------------------
# segment structure per family
# --------------------------------------------------------------------------

# a pattern's letters and the single-mixer blocks they stand for
PATTERN_KINDS = {"M": "mamba", "E": "moe", "*": "attn_only"}


def structure(cfg) -> list[tuple[str, int]]:
    """Returns [(block_kind, count), ...] covering cfg.n_layers."""
    L = cfg.n_layers
    if cfg.pattern:
        assert len(cfg.pattern) == L, (cfg.pattern, L)
        segs = []
        for c in cfg.pattern:
            kind = PATTERN_KINDS[c]
            if segs and segs[-1][0] == kind:
                segs[-1] = (kind, segs[-1][1] + 1)
            else:
                segs.append((kind, 1))
        return segs
    if cfg.family in ("dense", "vlm"):
        return [("attn", L)]
    if cfg.family == "moe":
        if cfg.moe.layer0_dense:
            return [("attn", 1), ("attn_moe", L - 1)]
        return [("attn_moe", L)]
    if cfg.family == "ssm":
        return [("mamba", L)]
    if cfg.family == "hybrid":
        segs: list[tuple[str, int]] = []
        period = cfg.hybrid.period
        remaining = L
        while remaining > 0:
            run = min(period, remaining)
            segs.append(("mamba", run))
            remaining -= run
            if remaining > 0 or run == period:
                segs.append(("shared_attn", 1))
        return segs
    raise ValueError(f"unknown family {cfg.family!r} (audio → encdec.py)")


def n_shared_applications(cfg) -> int:
    return sum(1 for k, _ in structure(cfg) if k == "shared_attn")


# --------------------------------------------------------------------------
# blocks
# --------------------------------------------------------------------------

def _block_init(key, cfg, dtype, kind: str) -> dict:
    ninit, _ = layers.norm(cfg.norm)
    if kind == "attn_only":
        return {"norm1": ninit(cfg.d_model, dtype),
                "attn": layers.attention_init(key, cfg, dtype)}
    if kind == "moe":
        return {"norm1": ninit(cfg.d_model, dtype),
                "moe": moe.moe_init(key, cfg, dtype)}
    if kind in ("attn", "attn_moe", "shared_attn"):
        ks = jax.random.split(key, 2)
        attn_init = layers.mla_init if cfg.mla else layers.attention_init
        p = {
            "norm1": ninit(cfg.d_model, dtype),
            "attn": attn_init(ks[0], cfg, dtype),
            "norm2": ninit(cfg.d_model, dtype),
        }
        if kind == "attn_moe":
            p["moe"] = moe.moe_init(ks[1], cfg, dtype)
        else:
            d_ff = cfg.d_ff
            if kind == "shared_attn" and cfg.hybrid and cfg.hybrid.shared_d_ff:
                d_ff = cfg.hybrid.shared_d_ff
            p["mlp"] = layers.mlp_init(ks[1], cfg, dtype, d_ff=d_ff)
        return p
    if kind == "mamba":
        return {
            "norm1": ninit(cfg.d_model, dtype),
            "mamba": mamba.mamba_init(key, cfg, dtype),
        }
    raise ValueError(kind)


def _block_apply(p: dict, cfg, x, positions, kind: str, cache=None,
                 advance=None):
    """Returns (x, aux, new_cache, counts): ``counts`` are an expert
    layer's counters (:func:`moe.moe_apply`), else empty."""
    _, napply = layers.norm(cfg.norm)
    nfn = functools.partial(napply, eps=cfg.norm_eps)
    aux = jnp.asarray(0.0, jnp.float32)
    counts = {}
    if kind == "moe":
        f, aux, counts = moe.moe_apply(p["moe"], cfg, nfn(p["norm1"], x))
        return x + f, aux, cache, counts
    if kind == "attn_only":
        a_out, new_cache = layers.attention(
            p["attn"], cfg, nfn(p["norm1"], x), positions=positions,
            causal=True, cache=cache, advance=advance)
        return x + a_out, aux, new_cache, counts
    if kind in ("attn", "attn_moe", "shared_attn"):
        h = nfn(p["norm1"], x)
        if cfg.mla:
            a_out, new_cache = layers.mla(p["attn"], cfg, h,
                                          positions=positions, cache=cache,
                                          advance=advance)
        else:
            a_out, new_cache = layers.attention(
                p["attn"], cfg, h, positions=positions, causal=True,
                cache=cache, mrope=cfg.mrope, advance=advance)
        x = x + a_out
        h2 = nfn(p["norm2"], x)
        if kind == "attn_moe":
            f, aux, counts = moe.moe_apply(p["moe"], cfg, h2)
        else:
            f = layers.mlp(p["mlp"], cfg, h2)
        return x + f, aux, new_cache, counts
    if kind == "mamba":
        h = nfn(p["norm1"], x)
        if cache is None:
            return x + mamba.mamba_apply(p["mamba"], cfg, h), aux, None, \
                counts
        if h.shape[1] > 1:  # prefill: full scan, then hand over the state
            out, new_cache = mamba.mamba_apply(p["mamba"], cfg, h,
                                               return_state=True)
        else:
            out, new_cache = mamba.mamba_decode_step(p["mamba"], cfg, h,
                                                     cache, advance=advance)
        return x + out, aux, new_cache, counts
    raise ValueError(kind)


def _block_cache(cfg, kind: str, batch: int, max_len: int, dtype):
    if kind in ("attn", "attn_moe", "shared_attn", "attn_only"):
        return layers.attention_cache(cfg, batch, max_len, dtype)
    if kind == "mamba":
        return mamba.mamba_cache(cfg, batch, dtype)
    if kind == "moe":
        return {}
    raise ValueError(kind)


# --------------------------------------------------------------------------
# params
# --------------------------------------------------------------------------

def _stacked_init(init_fn, key, n: int):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def init_params(cfg, key) -> dict:
    dtype = jnp.dtype(cfg.param_dtype)
    ninit, _ = layers.norm(cfg.norm)
    keys = jax.random.split(key, len(structure(cfg)) + 3)
    params: dict[str, Any] = {
        "embedding": layers.embedding_init(keys[0], cfg, dtype),
        "final_norm": ninit(cfg.d_model, dtype),
        "segments": [],
    }
    has_shared = any(k == "shared_attn" for k, _ in structure(cfg))
    if has_shared:
        params["shared_block"] = _block_init(keys[1], cfg, dtype,
                                             "shared_attn")
    for i, (kind, count) in enumerate(structure(cfg)):
        if kind == "shared_attn":
            params["segments"].append({})  # weights live in shared_block
            continue
        params["segments"].append(_stacked_init(
            lambda k, kk=kind: _block_init(k, cfg, dtype, kk),
            keys[i + 2], count))
    return params


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


# --------------------------------------------------------------------------
# forward / loss
# --------------------------------------------------------------------------

def _positions(cfg, tokens, offset=0):
    B, S = tokens.shape[:2]
    off = jnp.asarray(offset, jnp.int32)
    if off.ndim == 1:  # per-row offsets (continuous batching)
        off = off[:, None]
    pos = off + jnp.arange(S, dtype=jnp.int32)[None, :]
    pos = jnp.broadcast_to(pos, (B, S))
    if cfg.mrope:  # text-only stream: (t, h, w) identical (M-RoPE stub note)
        pos = jnp.broadcast_to(pos[..., None], (B, S, 3))
    return pos


def _scan_segment(cfg, seg_params, x, positions, kind: str):
    """lax.scan over a stacked homogeneous segment (train/prefill path)."""

    def body(carry, lp):
        h, aux = carry
        with jax.named_scope(kind):  # names the block's ops in a profile
            h2, a, _, counts = _block_apply(lp, cfg, h, positions, kind)
        return (h2, aux + a), counts

    if cfg.remat == "full":
        body = jax.checkpoint(body, prevent_cse=False)
    carry = (x, jnp.asarray(0.0, jnp.float32))
    (x, aux), counts = jax.lax.scan(body, carry, seg_params)
    return x, aux, counts


def hidden_states(cfg, params, tokens, *, positions=None,
                  input_embeds=None):
    """Backbone up to (and including) the final norm: (B,S,D), aux, and the
    expert layers' counters (:func:`moe.moe_apply`), each stacked over the
    expert layers in order: (expert layers, n) int32."""
    x = (layers.embed(params["embedding"], cfg, tokens)
         if input_embeds is None else input_embeds)
    pos = _positions(cfg, tokens) if positions is None else positions
    aux_total = jnp.asarray(0.0, jnp.float32)
    counts: dict[str, list] = {}
    for (kind, count), seg_p in zip(structure(cfg), params["segments"]):
        if kind == "shared_attn":
            x, a, _, _ = _block_apply(params["shared_block"], cfg, x, pos,
                                      kind)
            aux_total = aux_total + a
        else:
            x, a, c = _scan_segment(cfg, seg_p, x, pos, kind)
            aux_total = aux_total + a
            for name, v in c.items():
                counts.setdefault(name, []).append(v)
    _, napply = layers.norm(cfg.norm)
    x = napply(params["final_norm"], x, eps=cfg.norm_eps)
    return x, aux_total, {k: jnp.concatenate(v) for k, v in counts.items()}


def forward(cfg, params, tokens, *, positions=None, input_embeds=None):
    """Full-sequence forward (train / prefill-without-cache).

    Returns (logits, aux_loss)."""
    x, aux_total, _ = hidden_states(cfg, params, tokens, positions=positions,
                                    input_embeds=input_embeds)
    logits = layers.unembed(params["embedding"], cfg, x)
    return logits, aux_total


def _nll_dense(cfg, params, hidden, labels):
    logits = layers.unembed(params["embedding"], cfg, hidden)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - gold)


def _nll_chunked(cfg, params, hidden, labels):
    """Cross-entropy without materialising full (B,S,V) logits: scan over
    sequence chunks with rematerialisation — the §Perf memory lever.

    Peak logits memory drops from (B,S,V) to (B,loss_chunk,V)."""
    B, S, D = hidden.shape
    ck = cfg.loss_chunk
    nc = S // ck if S % ck == 0 else 1
    ck = S // nc
    h = jnp.moveaxis(hidden.reshape(B, nc, ck, D), 1, 0)
    l = jnp.moveaxis(labels.reshape(B, nc, ck), 1, 0)

    def body(acc, xs):
        hc, lc = xs
        return acc + _nll_dense(cfg, params, hc, lc), None

    body = jax.checkpoint(body, prevent_cse=False)
    total, _ = jax.lax.scan(body, jnp.asarray(0.0, jnp.float32), (h, l))
    return total


def loss_fn(cfg, params, batch, *, aux_weight: Optional[float] = None):
    """batch: {"tokens": (B,S), "labels": (B,S)} → (loss, metrics).

    ``aux_weight`` defaults to the config's balance-loss weight.  The
    metrics hold the expert layers' counters: ``held_expert_tokens``, the
    tokens routed to each held expert, (expert layers, n_held) int32, where
    the expert layers hold a share; ``expert_load``, the tokens that chose
    each expert, (expert layers, n_experts), where the router has a
    correction bias."""
    if aux_weight is None:
        aux_weight = cfg.moe.aux_weight if cfg.moe else 0.0
    hidden, aux, counts = hidden_states(cfg, params, batch["tokens"])
    labels = batch["labels"]
    B, S = labels.shape
    if cfg.loss_chunk and S > cfg.loss_chunk:
        total = _nll_chunked(cfg, params, hidden, labels)
    else:
        total = _nll_dense(cfg, params, hidden, labels)
    nll = total / (B * S)
    loss = nll + aux_weight * aux
    metrics = {"nll": nll, "aux": aux,
               "perplexity": jnp.exp(jnp.minimum(nll, 20.0)), **counts}
    return loss, metrics


# --------------------------------------------------------------------------
# serving: cache init / prefill / decode
# --------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int) -> dict:
    dtype = jnp.dtype(cfg.compute_dtype)
    cache: dict[str, Any] = {"segments": [],
                             "step": jnp.zeros((batch,), jnp.int32)}
    for kind, count in structure(cfg):
        if kind == "shared_attn":
            cache["segments"].append(
                _block_cache(cfg, kind, batch, max_len, dtype))
        else:
            cache["segments"].append(jax.vmap(
                lambda _: _block_cache(cfg, kind, batch, max_len, dtype)
            )(jnp.arange(count)))
    return cache


def _scan_segment_cached(cfg, seg_params, seg_cache, x, positions, kind,
                         advance=None):
    def body(carry, pc):
        lp, lc = pc
        h2, _, nc, _ = _block_apply(lp, cfg, carry, positions, kind,
                                    cache=lc, advance=advance)
        return h2, nc

    x, new_cache = jax.lax.scan(body, x, (seg_params, seg_cache))
    return x, new_cache


def decode_step(cfg, params, cache, tokens, *, positions=None, advance=None):
    """tokens: (B, S_step) (S_step=1 for pure decode).  Returns
    (logits, new_cache).  ``advance`` (B,) bool: continuous-batching rows."""
    x = layers.embed(params["embedding"], cfg, tokens)
    pos = (_positions(cfg, tokens, offset=cache["step"])
           if positions is None else positions)
    adv = (jnp.ones((tokens.shape[0],), bool)
           if advance is None else advance)
    new_cache: dict[str, Any] = {
        "segments": [],
        "step": cache["step"] + jnp.where(adv, tokens.shape[1], 0
                                          ).astype(jnp.int32)}
    for (kind, count), seg_p, seg_c in zip(
            structure(cfg), params["segments"], cache["segments"]):
        if kind == "shared_attn":
            x, _, nc, _ = _block_apply(params["shared_block"], cfg, x, pos,
                                       kind, cache=seg_c, advance=advance)
        else:
            x, nc = _scan_segment_cached(cfg, seg_p, seg_c, x, pos, kind,
                                         advance=advance)
        new_cache["segments"].append(nc)
    _, napply = layers.norm(cfg.norm)
    x = napply(params["final_norm"], x, eps=cfg.norm_eps)
    logits = layers.unembed(params["embedding"], cfg, x)
    return logits, new_cache


def prefill(cfg, params, tokens, max_len: int):
    """Process the prompt, building the cache.  Returns (logits, cache)."""
    cache = init_cache(cfg, tokens.shape[0], max_len)
    return decode_step(cfg, params, cache, tokens)


def reset_slot(cfg, cache, slot):
    """Zero one batch row of the cache (slot reuse in continuous batching).

    Cache leaves are (L, B, ...) for stacked segments and (B, ...) for the
    shared block, so the batch axis is 1 vs 0 respectively."""

    def zero_row(axis):
        def z(leaf):
            idx = [slice(None)] * leaf.ndim
            idx[axis] = slot
            return leaf.at[tuple(idx)].set(jnp.zeros((), leaf.dtype))
        return z

    new_segments = []
    for (kind, count), seg_c in zip(structure(cfg), cache["segments"]):
        axis = 0 if kind == "shared_attn" else 1
        new_segments.append(jax.tree_util.tree_map(zero_row(axis), seg_c))
    return {"segments": new_segments,
            "step": cache["step"].at[slot].set(0)}
