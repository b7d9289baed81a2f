"""Mamba2 block (SSD — state-space duality, arXiv:2405.21060).

Train/prefill uses the chunked SSD (kernels/ssd_scan: pure-jnp default,
Pallas kernel when ``cfg.use_pallas``); decode is the O(1)-per-token
recurrence on a carried (conv, ssd) state — the sub-quadratic property that
lets mamba2/zamba2 serve 500k-token contexts.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.parallel.axes import act
from . import layers
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.kernels.ssd_scan import ref as ssd_ref

__all__ = ["mamba_init", "mamba_apply", "mamba_cache", "mamba_decode_step"]


def _dims(cfg):
    s = cfg.ssm
    di = s.expand * cfg.d_model
    H = di // s.head_dim
    conv_dim = di + 2 * s.n_groups * s.d_state
    return di, H, s.head_dim, s.d_state, s.n_groups, conv_dim, s.conv_kernel


def mamba_init(key, cfg, dtype) -> dict:
    D = cfg.d_model
    di, H, P, N, G, conv_dim, ck = _dims(cfg)
    ks = jax.random.split(key, 4)
    proj_out = 2 * di + 2 * G * N + H  # z, xBC, dt
    return {
        "in_proj": layers.dense_init(ks[0], (D, proj_out), dtype),
        "conv_w": (jax.random.normal(ks[1], (ck, conv_dim)) * 0.1
                   ).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "A_log": jnp.zeros((H,), jnp.float32),  # A = -exp(A_log) = -1
        "D_skip": jnp.ones((H,), jnp.float32),
        "gate_norm": {"scale": jnp.ones((di,), dtype)},
        "out_proj": layers.dense_init(ks[3], (di, D), dtype,
                                      scale=1.0 / math.sqrt(di)),
    }


def _split_proj(cfg, proj):
    di, H, P, N, G, conv_dim, ck = _dims(cfg)
    z = proj[..., :di]
    xBC = proj[..., di:di + conv_dim]
    dt = proj[..., di + conv_dim:]
    return z, xBC, dt


def _causal_conv(xBC, w, b):
    """Depthwise causal conv over seq: xBC (B,S,C), w (k,C)."""
    k = w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(xBC, dtype=jnp.float32)
    S = xBC.shape[1]
    for j in range(k):
        out = out + pad[:, j:j + S, :].astype(jnp.float32) * w[j].astype(
            jnp.float32)
    return jax.nn.silu(out + b.astype(jnp.float32)).astype(xBC.dtype)


def mamba_apply(p: dict, cfg, x: jax.Array, *, return_state: bool = False):
    """x: (B, S, D) → (B, S, D).  Full-sequence (train / prefill) path.

    ``return_state=True`` (prefill) also returns the decode cache."""
    B, S, D = x.shape
    di, H, P, N, G, conv_dim, ck = _dims(cfg)
    proj = jnp.einsum("bsd,dk->bsk", x, p["in_proj"].astype(x.dtype))
    proj = act(proj, "batch", "seq", "ff")
    z, xBC_raw, dt = _split_proj(cfg, proj)
    xBC = _causal_conv(xBC_raw, p["conv_w"], p["conv_b"])
    xs = xBC[..., :di].reshape(B, S, H, P)
    Bm = xBC[..., di:di + G * N].reshape(B, S, G, N)
    Cm = xBC[..., di + G * N:].reshape(B, S, G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))  # (H,)
    res = ssd_ops.ssd(xs, dt, A, Bm, Cm, chunk=cfg.ssm.chunk,
                      use_pallas=cfg.use_pallas,
                      return_state=return_state)
    y, hT = res if return_state else (res, None)
    y = y + p["D_skip"].astype(jnp.float32)[None, None, :, None] * xs
    y = y.astype(x.dtype).reshape(B, S, di)
    # gated RMSNorm (mamba2): norm(y * silu(z))
    y = layers.rmsnorm(p["gate_norm"], y * jax.nn.silu(z), eps=cfg.norm_eps)
    out = jnp.einsum("bsk,kd->bsd", y, p["out_proj"].astype(x.dtype))
    out = act(out, "batch", "seq", "d")
    if return_state:
        pad = jnp.zeros((B, ck - 1, conv_dim), xBC_raw.dtype)
        conv_state = jnp.concatenate([pad, xBC_raw], axis=1)[:, -(ck - 1):]
        return out, {"conv": conv_state, "h": hT.reshape(B, H, N, P)}
    return out


def mamba_cache(cfg, batch: int, dtype) -> dict:
    di, H, P, N, G, conv_dim, ck = _dims(cfg)
    return {
        "conv": jnp.zeros((batch, ck - 1, conv_dim), dtype),
        "h": jnp.zeros((batch, H, N, P), jnp.float32),
    }


def mamba_decode_step(p: dict, cfg, x: jax.Array, cache: dict,
                      advance=None):
    """x: (B, 1, D) single step.  Returns (out (B,1,D), new_cache).

    ``advance`` (B,) bool: rows with False keep their old state (continuous
    batching: inactive slots)."""
    B = x.shape[0]
    di, H, P, N, G, conv_dim, ck = _dims(cfg)
    proj = jnp.einsum("bsd,dk->bsk", x, p["in_proj"].astype(x.dtype))
    z, xBC, dt = _split_proj(cfg, proj)  # (B,1,·)
    window = jnp.concatenate([cache["conv"], xBC], axis=1)  # (B, ck, C)
    conv_out = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                          p["conv_w"].astype(jnp.float32))
    xBC_t = jax.nn.silu(conv_out + p["conv_b"].astype(jnp.float32)
                        ).astype(x.dtype)  # (B, C)
    new_conv = window[:, 1:, :]
    xs = xBC_t[:, :di].reshape(B, H, P)
    Bm = xBC_t[:, di:di + G * N].reshape(B, G, N)
    Cm = xBC_t[:, di + G * N:].reshape(B, G, N)
    if G == 1:
        Bm = jnp.broadcast_to(Bm, (B, H, N))
        Cm = jnp.broadcast_to(Cm, (B, H, N))
    else:
        rep = H // G
        Bm = jnp.repeat(Bm, rep, axis=1)
        Cm = jnp.repeat(Cm, rep, axis=1)
    dtv = jax.nn.softplus(dt.astype(jnp.float32)[:, 0, :]
                          + p["dt_bias"].astype(jnp.float32))  # (B,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y, h_new = ssd_ref.ssd_decode_step(
        cache["h"].reshape(B * H, N, P), xs.reshape(B * H, P),
        dtv.reshape(B * H), jnp.tile(A, B), Bm.reshape(B * H, N),
        Cm.reshape(B * H, N))
    h_new = h_new.reshape(B, H, N, P)
    y = y.reshape(B, H, P) + p["D_skip"].astype(jnp.float32)[None, :, None] \
        * xs.astype(jnp.float32)
    y = y.reshape(B, 1, di).astype(x.dtype)
    y = layers.rmsnorm(p["gate_norm"], y * jax.nn.silu(z), eps=cfg.norm_eps)
    out = jnp.einsum("bsk,kd->bsd", y, p["out_proj"].astype(x.dtype))
    if advance is not None:
        keep = advance[:, None, None]
        new_conv = jnp.where(keep, new_conv, cache["conv"])
        h_new = jnp.where(advance[:, None, None, None], h_new, cache["h"])
    return out, {"conv": new_conv, "h": h_new}
