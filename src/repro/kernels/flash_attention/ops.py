"""Public flash-attention op: GQA head folding, seq padding, dispatch."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .kernel import flash_attention


def mha(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
        scale: float | None = None, block_q: int = 128, block_k: int = 128,
        use_pallas: bool = True) -> jax.Array:
    """q: (B, H, Sq, D); k, v: (B, K, Sk, D).  Same contract as ref.mha."""
    if not use_pallas:
        return ref.mha(q, k, v, causal=causal, scale=scale)
    B, H, Sq, D = q.shape
    K, Sk = k.shape[1], k.shape[2]
    group = H // K
    bq = min(block_q, _ceil_mult(Sq))
    bk = min(block_k, _ceil_mult(Sk))
    pq = (-Sq) % bq
    pk = (-Sk) % bk
    qf = q.reshape(B * H, Sq, D)
    kf = k.reshape(B * K, Sk, D)
    vf = v.reshape(B * K, Sk, D)
    if pq:
        qf = jnp.pad(qf, ((0, 0), (0, pq), (0, 0)))
    if pk:
        kf = jnp.pad(kf, ((0, 0), (0, pk), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pk), (0, 0)))
    # real lengths drive both the causal diagonal and the kv-padding mask;
    # padded q rows (at the end) are cropped from the output below.
    out = flash_attention(qf, kf, vf, causal=causal, scale=scale,
                          block_q=bq, block_k=bk, group=group,
                          q_real=Sq, kv_real=Sk)
    return out[:, :Sq, :].reshape(B, H, Sq, D)


def _ceil_mult(n: int, align: int = 128) -> int:
    """Largest power-of-two block ≤ align that divides-pads n sanely."""
    if n >= align:
        return align
    m = 8
    while m * 2 <= n:
        m *= 2
    return m
