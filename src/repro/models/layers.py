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
                sections: Optional[tuple] = None, yarn=None) -> tuple:
    """positions: (B, S) int — or (B, S, 3) for M-RoPE with ``sections``
    (t, h, w) summing to rot_dim // 2.  Returns cos, sin: (B, S, rot_dim/2).
    ``yarn`` (a :class:`~repro.configs.YarnConfig`) scales the frequencies
    and cos/sin as YaRN does (:func:`yarn_inv_freq`).
    """
    half = rot_dim // 2
    if yarn is None:
        inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32)
                               / half))
    else:
        inv = yarn_inv_freq(rot_dim, theta, yarn)
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
    if yarn is None:
        return jnp.cos(ang), jnp.sin(ang)
    m = (yarn_mscale(yarn.factor, yarn.mscale)
         / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature factor, ``0.1 mscale ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, yarn) -> jax.Array:
    """YaRN's rotary frequencies, (dim/2,): each frequency blends the plain
    one (``theta^(-2i/dim)``) and it divided by ``factor``; the blend ramps
    linearly between the correction dims ``lo`` and ``hi`` found from
    ``beta_fast`` and ``beta_slow`` rotations over the original context."""
    def corr(rotations):
        return (dim * math.log(yarn.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(corr(yarn.beta_fast)), 0)
    hi = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    if lo == hi:
        hi += 0.001
    extra = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo)
                    / (hi - lo), 0.0, 1.0)
    mask = 1.0 - ramp
    return extra / yarn.factor * (1.0 - mask) + extra * mask


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
          attn_chunk: int = 0, scale: Optional[float] = None) -> jax.Array:
    """q, k: (B,S|T,H|K,hd); v: (B,T,K,hd_v) → (B,S,H,hd_v).  BHSD under
    the hood."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if use_pallas:
        from repro.kernels.flash_attention import ops as fops
        ot = fops.mha(qt, kt, vt, causal=causal, scale=scale)
    elif attn_chunk:
        from repro.kernels.flash_attention import ref as fref
        ot = fref.mha_chunked(qt, kt, vt, causal=causal, scale=scale,
                              chunk=attn_chunk)
    else:
        from repro.kernels.flash_attention import ref as fref
        ot = fref.mha(qt, kt, vt, causal=causal, scale=scale)
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
        if cfg.kv_quant:
            kq, ks = _kv_quantize(k)
            vq, vs = _kv_quantize(v)
            ck_q = _cache_write(cache["k"], kq, idx)
            cv_q = _cache_write(cache["v"], vq, idx)
            cks = _cache_write(cache["k_scale"], ks, idx)
            cvs = _cache_write(cache["v_scale"], vs, idx)
            new_idx = idx + jnp.where(advance, S, 0).astype(idx.dtype)
            new_cache = {"k": ck_q, "v": cv_q, "k_scale": cks,
                         "v_scale": cvs, "index": new_idx}
            k = _kv_dequantize(ck_q, cks, x.dtype)
            v = _kv_dequantize(cv_q, cvs, x.dtype)
        else:
            ck = _cache_write(cache["k"], k, idx)
            cv = _cache_write(cache["v"], v, idx)
            new_idx = idx + jnp.where(advance, S, 0).astype(idx.dtype)
            new_cache = {"k": ck, "v": cv, "index": new_idx}
            k, v = ck, cv
        out = _attend_cached(q, k, v, idx, hd ** -0.5).astype(x.dtype)
    else:
        out = _sdpa(q, k, v, causal=causal, use_pallas=cfg.use_pallas,
                    attn_chunk=cfg.attn_chunk)
    out = out.reshape(B, S, H * hd)
    out = jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(x.dtype))
    return act(out, "batch", "seq", "d"), new_cache


def _attend_cached(q, k, v, idx, scale: float) -> jax.Array:
    """Attention over a cache: q (B,S,H,d), k (B,T,K,d), v (B,T,K,d_v);
    row b's queries sit at positions ``idx[b] + [0, S)`` and see the cache
    up to themselves.  GQA via grouped einsum — never materialise repeated
    (or f32) KV.  Returns (B,S,H,d_v) in float32."""
    B, S, H, d = q.shape
    T, K = k.shape[1], k.shape[2]
    group = H // K
    qg = q.reshape(B, S, K, group, d)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32) * scale
    ki = jnp.arange(T)[None, None, None, None, :]
    qi = (idx[:, None, None, None, None]
          + jnp.arange(S)[None, None, None, :, None])
    logits = jnp.where(ki <= qi, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    ot = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    return ot.reshape(B, S, H, v.shape[-1])


def _cache_write(c, new, idx):
    """Write ``new`` (B, S, ...) into cache ``c`` (B, T, ...) at each row's
    own position ``idx[b]``."""
    return jax.vmap(lambda cc, n, i: jax.lax.dynamic_update_slice_in_dim(
        cc, n, i, axis=0))(c, new, idx)


# --------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2)
# --------------------------------------------------------------------------

def mla_init(key, cfg, dtype) -> dict:
    """``wq`` (D, H·(nope+rope)): per head the no-rope then the rope part;
    ``wkva`` (D, rank+rope): the latent then the shared rope key; ``kv_norm``
    the latent's RMSNorm; ``wkvb`` (rank, H·(nope+v)): per head the key's
    no-rope part then the value; ``wo`` (H·v, D)."""
    m, D, H = cfg.mla, cfg.d_model, cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (D, H * qk), dtype),
        "wkva": dense_init(ks[1], (D, m.kv_lora_rank + m.qk_rope_head_dim),
                           dtype),
        "kv_norm": rmsnorm_init(m.kv_lora_rank, dtype),
        "wkvb": dense_init(ks[2], (m.kv_lora_rank,
                                   H * (m.qk_nope_head_dim + m.v_head_dim)),
                           dtype),
        "wo": dense_init(ks[3], (H * m.v_head_dim, D), dtype,
                         scale=1.0 / math.sqrt(H * m.v_head_dim)),
    }


def mla_scale(cfg) -> float:
    """Softmax scale: ``(nope+rope)^-1/2``, times YaRN's ``mscale_all_dim``
    factor squared where the rope is YaRN-scaled."""
    m = cfg.mla
    s = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    y = cfg.yarn
    if y is not None and y.mscale_all_dim:
        s *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return s


def mla(p: dict, cfg, x: jax.Array, *, positions: jax.Array,
        cache: Optional[dict] = None, advance: Optional[jax.Array] = None):
    """Causal multi-head latent attention (DeepSeek-V2, no query
    compression), keys and values decompressed per head.

    Rope rotates the two halves of each rope part (``apply_rope``); the
    published model rotates interleaved pairs, which is the same map once
    the rope columns of ``wq`` and ``wkva`` are permuted (evens, then odds).
    The cache (decode) holds each position's latent and rope key.  Returns
    (out, new_cache)."""
    m = cfg.mla
    B, S, _ = x.shape
    H, r = cfg.n_heads, m.kv_lora_rank
    nope, rope, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(x.dtype))
    q = act(q.reshape(B, S, H, nope + rope), "batch", "seq", "heads", None)
    kva = jnp.einsum("bsd,dh->bsh", x, p["wkva"].astype(x.dtype))
    c = rmsnorm(p["kv_norm"], kva[..., :r], cfg.norm_eps)
    cos, sin = rope_angles(positions, rope, cfg.rope_theta, yarn=cfg.yarn)
    q = jnp.concatenate(
        [q[..., :nope], apply_rope(q[..., nope:], cos, sin, rope)], axis=-1)
    k_pe = apply_rope(kva[..., None, r:], cos, sin, rope)[:, :, 0]
    new_cache = None
    if cache is not None:
        idx = cache["index"]
        if advance is None:
            advance = jnp.ones((B,), bool)
        c = _cache_write(cache["ckv"], c, idx)
        k_pe = _cache_write(cache["kpe"], k_pe, idx)
        new_cache = {"ckv": c, "kpe": k_pe,
                     "index": idx + jnp.where(advance, S, 0).astype(
                         idx.dtype)}
    T = c.shape[1]
    kv = jnp.einsum("btr,rh->bth", c, p["wkvb"].astype(x.dtype))
    kv = kv.reshape(B, T, H, nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe[:, :, None, :],
                                          (B, T, H, rope))], axis=-1)
    k = act(k, "batch", "seq", "heads", None)
    v = kv[..., nope:]
    if cache is not None:
        out = _attend_cached(q, k, v, cache["index"],
                             mla_scale(cfg)).astype(x.dtype)
    else:
        out = _sdpa(q, k, v, causal=True, use_pallas=cfg.use_pallas,
                    attn_chunk=cfg.attn_chunk, scale=mla_scale(cfg))
    out = jnp.einsum("bsh,hd->bsd", out.reshape(B, S, H * dv),
                     p["wo"].astype(x.dtype))
    return act(out, "batch", "seq", "d"), new_cache


def attention_cache(cfg, batch: int, max_len: int, dtype) -> dict:
    if cfg.mla:  # the latent and the shared rope key of each position
        m = cfg.mla
        return {
            "ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
            "kpe": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype),
            "index": jnp.zeros((batch,), jnp.int32),
        }
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
    if cfg.act == "relu2":  # not gated (Nemotron-H)
        return {
            "up": dense_init(ks[0], (D, F), dtype),
            "down": dense_init(ks[1], (F, D), dtype, scale=1.0 / math.sqrt(F)),
        }
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


def relu2(x: jax.Array) -> jax.Array:
    """Squared ReLU."""
    return jnp.square(jax.nn.relu(x))


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
    elif kind == "relu2":
        h = jnp.einsum("bsd,df->bsf", x, p["up"].astype(x.dtype))
        h = relu2(act(h, "batch", "seq", "ff"))
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
