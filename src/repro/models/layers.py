"""Neural building blocks shared by all architectures.

Parameter trees are plain dicts with conventional leaf names; the sharding
layer (parallel/sharding.py) assigns PartitionSpecs by those names, and the
activation annotations route through parallel/axes.py (no-ops off-mesh).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.parallel.axes import act

# --------------------------------------------------------------------------
# init helpers
# --------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


def norm(kind: str):
    return {"rmsnorm": (rmsnorm_init, rmsnorm),
            "layernorm": (layernorm_init, layernorm)}[kind]


# --------------------------------------------------------------------------
# rotary embeddings (standard, fractional, and M-RoPE)
# --------------------------------------------------------------------------

def rope_angles(positions: jax.Array, rot_dim: int, theta: float,
                sections: Optional[tuple] = None) -> tuple:
    """positions: (B, S) int — or (B, S, 3) for M-RoPE with ``sections``
    (t, h, w) summing to rot_dim // 2.  Returns cos, sin: (B, S, rot_dim/2).
    """
    half = rot_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if sections is None:
        ang = positions.astype(jnp.float32)[..., None] * inv  # (B,S,half)
    else:
        assert sum(sections) == half, (sections, half)
        sec_of = jnp.repeat(jnp.arange(3), jnp.asarray(sections),
                            total_repeat_length=half)  # (half,) section idx
        pos = jnp.take_along_axis(
            positions.astype(jnp.float32),
            jnp.broadcast_to(sec_of[None, None, :],
                             positions.shape[:2] + (half,)).astype(jnp.int32),
            axis=-1)  # (B,S,half): per-freq position stream
        ang = pos * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               rot_dim: int) -> jax.Array:
    """x: (B, S, H, hd); rotate the first rot_dim dims (half-split layout)."""
    rot, rest = x[..., :rot_dim], x[..., rot_dim:]
    half = rot_dim // 2
    x1, x2 = rot[..., :half], rot[..., half:]
    c = cos[:, :, None, :].astype(jnp.float32)
    s = sin[:, :, None, :].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    r1 = x1f * c - x2f * s
    r2 = x2f * c + x1f * s
    out = jnp.concatenate([r1.astype(x.dtype), r2.astype(x.dtype)], axis=-1)
    return jnp.concatenate([out, rest], axis=-1) if rest.shape[-1] else out


# --------------------------------------------------------------------------
# attention (GQA, optional KV cache, flash kernel dispatch)
# --------------------------------------------------------------------------

def attention_init(key, cfg, dtype) -> dict:
    D, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (D, H * hd), dtype),
        "wk": dense_init(ks[1], (D, K * hd), dtype),
        "wv": dense_init(ks[2], (D, K * hd), dtype),
        "wo": dense_init(ks[3], (H * hd, D), dtype, scale=1.0 / math.sqrt(H * hd)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((K * hd,), dtype)
        p["bv"] = jnp.zeros((K * hd,), dtype)
    return p


def _sdpa(q, k, v, *, causal: bool, use_pallas: bool,
          attn_chunk: int = 0) -> jax.Array:
    """q: (B,S,H,hd); k,v: (B,T,K,hd) → (B,S,H,hd).  BHSD under the hood."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if use_pallas:
        from repro.kernels.flash_attention import ops as fops
        ot = fops.mha(qt, kt, vt, causal=causal)
    elif attn_chunk:
        from repro.kernels.flash_attention import ref as fref
        ot = fref.mha_chunked(qt, kt, vt, causal=causal, chunk=attn_chunk)
    else:
        from repro.kernels.flash_attention import ref as fref
        ot = fref.mha(qt, kt, vt, causal=causal)
    return jnp.swapaxes(ot, 1, 2)


def attention(p: dict, cfg, x: jax.Array, *, positions: jax.Array,
              causal: bool = True, cache: Optional[dict] = None,
              kv_input: Optional[jax.Array] = None,
              mrope: bool = False, advance: Optional[jax.Array] = None):
    """Self (or cross, via ``kv_input``) attention.

    With ``cache`` (decode): append this step's k/v at the *per-row*
    ``cache["index"]`` and attend over each row's valid prefix.  ``advance``
    (B,) bool selects which rows commit their index (continuous batching:
    inactive slots rewrite in place).  Returns (out, new_cache).
    """
    B, S, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    src = x if kv_input is None else kv_input
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dh->bsh", src, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dh->bsh", src, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, -1, K, hd)
    v = v.reshape(B, -1, K, hd)
    q = act(q, "batch", "seq", "heads", None)
    k = act(k, "batch", "seq", "heads", None)
    if kv_input is None:  # RoPE only for self-attention
        rot = int(cfg.hd * cfg.rope_fraction) // 2 * 2
        if rot:
            sections = cfg.mrope_sections if mrope else None
            cos, sin = rope_angles(positions, rot, cfg.rope_theta, sections)
            q = apply_rope(q, cos, sin, rot)
            k = apply_rope(k, cos, sin, rot)
    new_cache = None
    if cache is not None:
        idx = cache["index"]  # (B,) per-row write position
        if advance is None:
            advance = jnp.ones((B,), bool)
        upd = jax.vmap(
            lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(
                c, n, i, axis=0))
        if cfg.kv_quant:
            kq, ks = _kv_quantize(k)
            vq, vs = _kv_quantize(v)
            ck_q = upd(cache["k"], kq, idx)
            cv_q = upd(cache["v"], vq, idx)
            cks = upd(cache["k_scale"], ks, idx)
            cvs = upd(cache["v_scale"], vs, idx)
            new_idx = idx + jnp.where(advance, S, 0).astype(idx.dtype)
            new_cache = {"k": ck_q, "v": cv_q, "k_scale": cks,
                         "v_scale": cvs, "index": new_idx}
            k = _kv_dequantize(ck_q, cks, x.dtype)
            v = _kv_dequantize(cv_q, cvs, x.dtype)
        else:
            ck = upd(cache["k"], k, idx)
            cv = upd(cache["v"], v, idx)
            new_idx = idx + jnp.where(advance, S, 0).astype(idx.dtype)
            new_cache = {"k": ck, "v": cv, "index": new_idx}
            k, v = ck, cv
        # per-row causality: row b's queries sit at positions idx_b + [0,S).
        # GQA via grouped einsum — never materialise repeated (or f32) KV.
        T = k.shape[1]
        group = H // K
        qg = q.reshape(B, S, K, group, hd)
        logits = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                            preferred_element_type=jnp.float32) * (hd ** -0.5)
        ki = jnp.arange(T)[None, None, None, None, :]
        qi = (idx[:, None, None, None, None]
              + jnp.arange(S)[None, None, None, :, None])
        logits = jnp.where(ki <= qi, logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        ot = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v,
                        preferred_element_type=jnp.float32)
        out = ot.reshape(B, S, H, hd).astype(x.dtype)
    else:
        out = _sdpa(q, k, v, causal=causal, use_pallas=cfg.use_pallas,
                    attn_chunk=cfg.attn_chunk)
    out = out.reshape(B, S, H * hd)
    out = jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(x.dtype))
    return act(out, "batch", "seq", "d"), new_cache


def attention_cache(cfg, batch: int, max_len: int, dtype) -> dict:
    K, hd = cfg.n_kv_heads, cfg.hd
    if cfg.kv_quant:  # int8 payload + per-(pos, head) scale: ~2x smaller
        return {
            "k": jnp.zeros((batch, max_len, K, hd), jnp.int8),
            "v": jnp.zeros((batch, max_len, K, hd), jnp.int8),
            "k_scale": jnp.zeros((batch, max_len, K), jnp.float32),
            "v_scale": jnp.zeros((batch, max_len, K), jnp.float32),
            "index": jnp.zeros((batch,), jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, max_len, K, hd), dtype),
        "v": jnp.zeros((batch, max_len, K, hd), dtype),
        "index": jnp.zeros((batch,), jnp.int32),
    }


def _kv_quantize(x):
    """x: (B, S, K, hd) → int8 payload + (B, S, K) scale."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _kv_dequantize(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------

def mlp_init(key, cfg, dtype, d_ff: Optional[int] = None) -> dict:
    D = cfg.d_model
    F = d_ff if d_ff is not None else cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.act in ("swiglu", "geglu"):
        return {
            "gate": dense_init(ks[0], (D, F), dtype),
            "up": dense_init(ks[1], (D, F), dtype),
            "down": dense_init(ks[2], (F, D), dtype, scale=1.0 / math.sqrt(F)),
        }
    return {  # plain gelu MLP (whisper)
        "up": dense_init(ks[0], (D, F), dtype),
        "up_b": jnp.zeros((F,), dtype),
        "down": dense_init(ks[1], (F, D), dtype, scale=1.0 / math.sqrt(F)),
        "down_b": jnp.zeros((D,), dtype),
    }


def mlp(p: dict, cfg, x: jax.Array, *, act_fn: Optional[str] = None):
    kind = act_fn or cfg.act
    if kind in ("swiglu", "geglu"):
        g = jnp.einsum("bsd,df->bsf", x, p["gate"].astype(x.dtype))
        u = jnp.einsum("bsd,df->bsf", x, p["up"].astype(x.dtype))
        g = act(g, "batch", "seq", "ff")
        u = act(u, "batch", "seq", "ff")
        h = (jax.nn.silu(g) if kind == "swiglu"
             else jax.nn.gelu(g, approximate=True)) * u
        out = jnp.einsum("bsf,fd->bsd", h, p["down"].astype(x.dtype))
    else:
        h = jnp.einsum("bsd,df->bsf", x, p["up"].astype(x.dtype))
        h = act(h, "batch", "seq", "ff") + p["up_b"].astype(x.dtype)
        h = jax.nn.gelu(h, approximate=True)
        out = (jnp.einsum("bsf,fd->bsd", h, p["down"].astype(x.dtype))
               + p["down_b"].astype(x.dtype))
    return act(out, "batch", "seq", "d")


# --------------------------------------------------------------------------
# embedding / unembedding
# --------------------------------------------------------------------------

def embedding_init(key, cfg, dtype) -> dict:
    p = {"embed": embed_init(key, (cfg.vocab, cfg.d_model), dtype)}
    if not cfg.tied_embeddings:
        k2 = jax.random.fold_in(key, 1)
        p["lm_head"] = dense_init(k2, (cfg.d_model, cfg.vocab), dtype)
    return p


def embed(p: dict, cfg, tokens: jax.Array) -> jax.Array:
    x = jnp.take(p["embed"], tokens, axis=0).astype(
        jnp.dtype(cfg.compute_dtype))
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return act(x, "batch", "seq", "d")


def unembed(p: dict, cfg, x: jax.Array) -> jax.Array:
    w = (p["embed"].T if cfg.tied_embeddings else p["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))
    return act(logits, "batch", "seq", "vocab")
