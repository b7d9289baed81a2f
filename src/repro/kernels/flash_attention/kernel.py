"""Pallas TPU flash attention (causal, GQA), MaxText-style blocking.

TPU adaptation of the FlashAttention algorithm: the GPU version tiles into
SM shared memory with warp-level softmax reductions; here each grid step
streams one (block_q × block_k) tile pair HBM→VMEM and the MXU computes the
two GEMMs, with the streaming-softmax carry (m, l, acc) held in VMEM scratch
that persists across the innermost (kv) grid dimension — TPU grids execute
sequentially over the last axis, which *is* the flash inner loop.

Grid: (B·H, Sq/block_q, Sk/block_k).  GQA is folded into the k/v index maps
(query head h reads kv head h // group) so no repeated-KV materialisation
ever happens.  Causal blocks strictly above the diagonal are skipped with
``pl.when`` (compute + write suppressed), the diagonal block gets the
element mask — skipping halves the work exactly as the paper's farm skips
empty partitions.

All matmul dims should be multiples of 128 for MXU alignment; ops.py pads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import use_interpreter

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  q_real: int, kv_real: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causality is aligned to the *real* sequence ends (decode: Sq << Sk);
    # padded q rows live past q_real (cropped by ops), padded k columns past
    # kv_real are masked here.
    diag = kv_real - q_real

    def body():
        q = q_ref[0].astype(jnp.float32)  # (bq, D)
        k = k_ref[0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0].astype(jnp.float32)  # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        qi = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        ki = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = ki < kv_real
        if causal:
            mask = mask & (ki <= qi + diag)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    # block skip: beyond the causal frontier of the last real q row in this
    # block, or entirely past the real kv length.
    needed = (ik * block_k) < kv_real
    if causal:
        last_q = jnp.minimum(iq * block_q + block_q - 1, q_real - 1)
        needed = needed & ((ik * block_k) <= (last_q + diag))
    pl.when(needed)(body)

    @pl.when(ik == nk - 1)
    def _emit():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows → zeros
        o_ref[0, ...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "group",
                     "q_real", "kv_real"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128, group: int = 1,
                    q_real: int | None = None,
                    kv_real: int | None = None) -> jax.Array:
    """q: (BH, Sq, D); k, v: (BK, Sk, D) with BH == BK * group.

    Heads are pre-folded into the batch dim by ops.py; ``group`` = H // K.
    ``q_real``/``kv_real`` give the unpadded lengths (default: no padding).
    """
    BH, Sq, D = q.shape
    BK, Sk, _ = k.shape
    assert BH == BK * group, (BH, BK, group)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    q_real = Sq if q_real is None else q_real
    kv_real = Sk if kv_real is None else kv_real
    if causal:
        assert q_real <= kv_real, "causal requires q_real <= kv_real"
    scale = scale if scale is not None else D ** -0.5
    grid = (BH, Sq // block_q, Sk // block_k)
    kern = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, q_real=q_real, kv_real=kv_real)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, iq, ik: (bh // group, ik, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, iq, ik: (bh // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=[
            # VMEM carries persisting across the (sequential) kv grid axis
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        interpret=use_interpreter(),
    )(q, k, v)
