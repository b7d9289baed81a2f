"""Pure-jnp oracle: causal GQA attention with f32 softmax accumulation."""

from __future__ import annotations

import functools

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


# jitted: the unrolled chunks take seconds to trace, and every layer of a
# model that calls this with one shape then shares one trace
@functools.partial(jax.jit, static_argnames=("causal", "scale", "chunk"))
def mha_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                causal: bool = True, scale: float | None = None,
                chunk: int = 256) -> jax.Array:
    """Query-chunked attention: identical output to :func:`mha`, but the
    (Sq × Sk) logits never materialise — peak is (chunk × Sk) per chunk.

    The pure-XLA flash analogue for long prefill/train sequences (the Pallas
    kernel is the TPU-native version).
    Under ``causal`` each query chunk takes its logits, softmax and P·V over
    only the keys up to its diagonal, a static prefix, so n chunks compute
    (n+1)/2n of the key blocks; the mask then changes only the diagonal
    block.  The dropped keys' weights were exactly zero, and each chunk's
    softmax runs over its whole key extent with no online-softmax carry, so
    the result is the same function.  The chunks are unrolled (their extents
    differ) and each is rematerialised: the backward recomputes one chunk's
    probabilities at a time, last chunk first, instead of keeping every
    chunk's.  The backward is written out in the usual form, with
    rowsum(dP * P) taken as rowsum(dO * O).
    """
    B, H, Sq, D = q.shape
    K, Sk = k.shape[1], k.shape[2]
    group = H // K
    scale = scale if scale is not None else D ** -0.5
    if Sq % chunk != 0 or Sq <= chunk:
        return mha(q, k, v, causal=causal, scale=scale)
    qg = q.reshape(B, K, group, Sq, D)
    diag = Sk - Sq

    def probs(qc, k, q0, end):
        logits = jnp.einsum("bkgqd,bkld->bkgql", qc, k[:, :, :end],
                            preferred_element_type=jnp.float32) * scale
        if causal:
            qi = jnp.arange(chunk)[:, None] + q0 + diag
            ki = jnp.arange(end)[None, :]
            logits = jnp.where((ki <= qi)[None, None, None], logits,
                               -jnp.inf)
        return _softmax(logits)

    def forward(qc, k, v, q0, end):
        p = probs(qc, k, q0, end).astype(v.dtype)
        out = jnp.einsum("bkgql,bkld->bkgqd", p, v[:, :, :end],
                         preferred_element_type=jnp.float32)
        return jax.lax.optimization_barrier((out.astype(q.dtype), k, v))

    # k and v pass from each chunk to the next, behind its output, so the
    # chunks run in order and the later chunks' gradient of k and v reaches
    # a chunk's backward as a cotangent
    step = jax.custom_vjp(forward, nondiff_argnums=(3, 4))

    def step_fwd(qc, k, v, q0, end):
        out = forward(qc, k, v, q0, end)
        return out, (qc, k, v, out[0])

    def step_bwd(q0, end, res, cts):
        # the barrier holds the recompute until the later chunks are done:
        # without it the compiler may run every chunk's at once.  Written
        # out, the backward traces in half of jax.vjp's time.
        qc, k, v, o, do, dk, dv = jax.lax.optimization_barrier(
            (*res, *cts))
        f32 = jnp.float32
        p = probs(qc, k, q0, end)
        dp = jnp.einsum("bkgqd,bkld->bkgql", do, v[:, :, :end],
                        preferred_element_type=f32)
        delta = jnp.sum(do.astype(f32) * o.astype(f32), -1, keepdims=True)
        ds = (p * (dp - delta) * scale).astype(qc.dtype)
        dq = jnp.einsum("bkgql,bkld->bkgqd", ds, k[:, :, :end],
                        preferred_element_type=f32)
        grow = ((0, 0), (0, 0), (0, k.shape[2] - end), (0, 0))
        dk_c = jnp.einsum("bkgql,bkgqd->bkld", ds, qc,
                          preferred_element_type=f32)
        dv_c = jnp.einsum("bkgql,bkgqd->bkld", p.astype(v.dtype), do,
                          preferred_element_type=f32)
        return (dq.astype(qc.dtype),
                (dk + jnp.pad(dk_c, grow)).astype(k.dtype),
                (dv + jnp.pad(dv_c, grow)).astype(v.dtype))

    step.defvjp(step_fwd, step_bwd)
    blocks = []
    for q0 in range(0, Sq, chunk):
        end = q0 + chunk + diag if causal else Sk
        out, k, v = step(qg[:, :, :, q0:q0 + chunk], k, v, q0, end)
        blocks.append(out)
    out = jnp.concatenate(blocks, axis=3)  # (B,K,G,Sq,Dv)
    return out.reshape(B, H, Sq, v.shape[-1])
