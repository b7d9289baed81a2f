"""Pure-jnp oracle: causal GQA attention with f32 softmax accumulation."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _softmax(logits: jax.Array) -> jax.Array:
    """Softmax over the last axis as ``exp(x - logsumexp(x))``.  Written
    through ``logsumexp`` because the TPU compiler turns the usual
    max-subtract-normalise form of a long masked row into a sliding-window
    max over the whole row (a ``reduce-window`` of twice the row's length),
    some forty times slower than the dot that feeds it."""
    return jnp.exp(logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True))


def mha(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
        scale: float | None = None) -> jax.Array:
    """q: (B, H, Sq, D); k: (B, K, Sk, D); v: (B, K, Sk, Dv) with K | H.
    Returns (B, H, Sq, Dv).

    Grouped-query attention: query head h attends with kv head h // (H // K).
    The value head dim may differ from the query/key one (latent attention).
    """
    B, H, Sq, D = q.shape
    K = k.shape[1]
    Sk = k.shape[2]
    assert H % K == 0, (H, K)
    group = H // K
    scale = scale if scale is not None else D ** -0.5
    # GQA via grouped einsum — repeated KV is never materialised
    qg = q.reshape(B, K, group, Sq, D)
    logits = jnp.einsum("bkgqd,bkld->bkgql", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        # align the causal diagonal to the *end* of the kv sequence, so a
        # single new query with a long KV cache (decode) attends everywhere
        qi = jnp.arange(Sq)[:, None] + (Sk - Sq)
        ki = jnp.arange(Sk)[None, :]
        logits = jnp.where((ki <= qi)[None, None, None], logits, -jnp.inf)
    probs = _softmax(logits)
    out = jnp.einsum("bkgql,bkld->bkgqd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, Sq, v.shape[-1]).astype(q.dtype)


def mha_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                causal: bool = True, scale: float | None = None,
                chunk: int = 256) -> jax.Array:
    """Query-chunked attention: identical output to :func:`mha`, but the
    (Sq × Sk) logits never materialise — peak is (chunk × Sk) per step.

    The pure-XLA flash analogue for long prefill/train sequences (the Pallas
    kernel is the TPU-native version; this one also fixes the dry-run's
    memory picture since Mosaic kernels are opaque to the CPU backend).
    Softmax per q-chunk runs over the full key axis, so no online-softmax
    carry is needed — exactness is structural.  The chunk body is
    rematerialised: under autodiff the backward recomputes one chunk's
    probabilities at a time instead of keeping every chunk's.
    """
    B, H, Sq, D = q.shape
    K, Sk = k.shape[1], k.shape[2]
    group = H // K
    scale = scale if scale is not None else D ** -0.5
    if Sq % chunk != 0 or Sq <= chunk:
        return mha(q, k, v, causal=causal, scale=scale)
    nc = Sq // chunk
    qg = q.reshape(B, K, group, nc, chunk, D)
    qb = jnp.moveaxis(qg, 3, 0)  # (nc, B, K, G, chunk, D)
    diag = Sk - Sq
    ki = jnp.arange(Sk)[None, :]

    def body(carry, xs):
        qc, blk = xs  # (B,K,G,chunk,D), scalar block idx
        logits = jnp.einsum("bkgqd,bkld->bkgql", qc, k,
                            preferred_element_type=jnp.float32) * scale
        if causal:
            qi = blk * chunk + jnp.arange(chunk)[:, None] + diag
            logits = jnp.where((ki <= qi)[None, None, None], logits,
                               -jnp.inf)
        probs = _softmax(logits)
        out = jnp.einsum("bkgql,bkld->bkgqd", probs.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        return carry, out.astype(q.dtype)

    body = jax.checkpoint(body, prevent_cse=False)
    _, blocks = jax.lax.scan(body, None,
                             (qb, jnp.arange(nc, dtype=jnp.int32)))
    out = jnp.moveaxis(blocks, 0, 3)  # (B,K,G,nc,chunk,Dv)
    return out.reshape(B, H, Sq, v.shape[-1])
