"""Pure-jnp oracles for the Mamba2 SSD (state-space duality) scan.

``ssd_naive`` — the literal per-step recurrence (gold oracle):

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * B_t ⊗ x_t        h: (N, P)
    y_t = C_t · h_t

``ssd_chunked`` — the SSD chunked form (intra-chunk dual "attention" matmuls
+ inter-chunk state recurrence, one scan step a chunk) over heads that share
B and C in state groups, pure jnp; this is the model's default path and is
algebraically identical to ``ssd_naive`` on each head.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def ssd_naive(x, dt, A, B, C):
    """x: (BH, S, P); dt: (BH, S); A: (BH,) (negative); B, C: (BH, S, N).

    Returns y: (BH, S, P), final state h: (BH, N, P)."""
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Bf = B.astype(jnp.float32)
    Cf = C.astype(jnp.float32)

    def per_seq(xs, dts, a, Bs, Cs):
        N = Bs.shape[-1]
        P = xs.shape[-1]

        def step(h, inp):
            xt, dtt, bt, ct = inp
            da = jnp.exp(dtt * a)
            h = da * h + dtt * jnp.outer(bt, xt)
            y = ct @ h
            return h, y

        h0 = jnp.zeros((N, P), jnp.float32)
        hT, ys = jax.lax.scan(step, h0, (xs, dts, Bs, Cs))
        return ys, hT

    ys, hT = jax.vmap(per_seq)(xf, dtf, A.astype(jnp.float32), Bf, Cf)
    return ys.astype(x.dtype), hT


def ssd_chunked(x, dt, A, B, C, *, chunk: int = 64):
    """Chunked SSD over state groups.  x: (b, S, H, P); dt: (b, S, H);
    A: (H,) (negative); B, C: (b, S, G, N) with G | H: head h reads group
    h // (H/G).  S % chunk == 0 required.  Returns y: (b, S, H, P) and the
    final state (b, H, N, P).

    One scan step a chunk, rematerialised: the chunk's outputs from its
    intra-chunk "attention" (``C_t . B_s`` once per group) and the state
    entering it, then the state leaving it.  Only that state is kept
    between steps, so the memory is one chunk's, forward and backward.
    Within a chunk the decay from s to t is exp(cum_t - cum_s); the
    exponent is masked to -inf above the diagonal *before* exp, so no
    entry overflows and the gradient stays finite however large a chunk's
    summed log-decay grows."""
    b, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    R, L = H // G, chunk
    assert S % L == 0 and H % G == 0, (S, L, H, G)
    nc = S // L
    f32 = jnp.float32

    def chunks(a, *tail):  # (b, S, ...) -> (nc, b, L, ...)
        return jnp.moveaxis(a.reshape((b, nc, L) + tail), 1, 0)

    xs = chunks(x, G, R, P)
    dts = chunks(dt.astype(f32), G, R)
    Bs, Cs = chunks(B, G, N), chunks(C, G, N)
    Ar = A.astype(f32).reshape(G, R)
    causal = jnp.tril(jnp.ones((L, L), bool))

    def step(h, inp):  # h: (b, G, R, N, P), the state entering the chunk
        xc, dtc, Bc, Cc = inp
        xc, Bc, Cc = (v.astype(f32) for v in (xc, Bc, Cc))
        dtc = jnp.moveaxis(dtc, 1, -1)  # (b, G, R, L)
        cum = jnp.cumsum(dtc * Ar[..., None], axis=-1)  # inclusive
        total = cum[..., -1]  # (b, G, R)
        # y[t] = sum_{s<=t} exp(cum t - cum s) dt_s (C_t.B_s) x_s
        #        + (C_t exp(cum t)) . h
        CB = jnp.einsum("btgn,bsgn->bgts", Cc, Bc)
        seg = jnp.where(causal, cum[..., :, None] - cum[..., None, :],
                        -jnp.inf)
        W = CB[:, :, None] * jnp.exp(seg) * dtc[..., None, :]
        y = (jnp.einsum("bgrts,bsgrp->btgrp", W, xc)
             + jnp.einsum("btgn,bgrt,bgrnp->btgrp", Cc, jnp.exp(cum), h))
        # h' = exp(total) h + sum_s exp(total - cum s) dt_s B_s (x) x_s
        w_state = jnp.exp(total[..., None] - cum) * dtc
        h = (jnp.exp(total)[..., None, None] * h
             + jnp.einsum("bsgn,bgrs,bsgrp->bgrnp", Bc, w_state, xc))
        return h, y.astype(x.dtype)

    step = jax.checkpoint(step, prevent_cse=False)
    hT, ys = jax.lax.scan(step, jnp.zeros((b, G, R, N, P), f32),
                          (xs, dts, Bs, Cs))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, S, H, P)
    return y, hT.reshape(b, H, N, P)


def ssd_decode_step(h, x_t, dt_t, A, B_t, C_t):
    """Single recurrent decode step.  h: (BH, N, P); x_t: (BH, P);
    dt_t: (BH,); B_t, C_t: (BH, N).  Returns (y_t, h_new)."""
    da = jnp.exp(dt_t.astype(jnp.float32) * A.astype(jnp.float32))  # (BH,)
    h_new = (da[:, None, None] * h
             + dt_t[:, None, None].astype(jnp.float32)
             * jnp.einsum("bn,bp->bnp", B_t.astype(jnp.float32),
                          x_t.astype(jnp.float32)))
    y = jnp.einsum("bn,bnp->bp", C_t.astype(jnp.float32), h_new)
    return y.astype(x_t.dtype), h_new
