"""Plain float32 reference of DeepSeek-V2 training: forward, loss and (by
``jax.grad``) gradients, and the benchmark's weights.

Follows the published description (arXiv:2405.04434; the Hugging Face
``DeepseekV2ForCausalLM``) in its published form.  Per layer: RMSNorm, then
multi-head latent attention without query compression: ``q = h W_q`` split
per head into a no-rope and a rope part; ``[c~, k~_pe] = h W_kva``, the
latent ``c = RMSNorm(c~)`` and one rope key ``k_pe`` shared by all heads;
``[k_nope_h, v_h] = c W_kvb`` decompressed per head; causal softmax of
``q_h . [k_nope_h, k_pe]`` times ``(nope+rope)^-1/2 m^2`` (YaRN's
``m = 0.1 mscale_all_dim ln(factor) + 1``); the heads' weighted values
through ``W_o``.  Rope rotates interleaved pairs ``(2i, 2i+1)`` at YaRN's
frequencies.  A residual add, RMSNorm, then the dense SiLU-gated MLP in the
first ``first_k_dense_replace`` layers and the expert layer in the rest:
``p = softmax(h W_r)`` over every routed expert, the top ``k`` of ``p``
(renormalised only where ``norm_topk_prob``), ``y = sum_{e in top k} p_e
E_e(h) + S(h)`` with ``S`` the shared experts as one MLP.  A final RMSNorm
and the untied head.  The loss is the mean next-token cross-entropy plus
``aux_loss_alpha`` times, for each expert layer, the expert-level balance
loss of each sequence ``sum_i f_i P_i`` (``f_i = E/(k S) #{t: i in top k}``,
``P_i`` the mean of ``p_i``), averaged over the sequences.

Departures, each shared with the program under test:

* held experts: the configuration's ``n_routed_experts`` experts are held,
  experts ``first .. first + n_routed_experts - 1`` of the
  ``n_routed_experts_published`` the router scores; the part the other
  experts would add is left out (one chip's share of expert parallelism);
* ``vocab_size`` may be a slice of the published vocabulary;
* no dropout, no bias anywhere (the published model has none).

Every matrix product is float32 at the highest precision.  There are no
kernels, no cache and no batching: one sequence at a time.  The work is
evaluated in blocks so that it fits the chip: each layer and each block of
``BLOCK`` query rows (and of output-head rows) under ``jax.checkpoint``;
the arithmetic is the same.  ``mode="fp8"`` is the control: every product's
operands rounded to float8 e4m3, the precision below the bfloat16 the
configuration computes in.

Weights are random from a seed, made on the device in one jitted call in
float32, the type the program holds them in.

The optimizer step (:func:`adamw_update`, with :func:`clip_grads`) is
AdamW with decoupled weight decay (Loshchilov and Hutter) after clipping
the gradients to a global L2 norm, at the learning rate of a linear warmup,
from the file's ``train`` keys, in float32 on any tree of arrays;
``mode="bf16"`` is its control, every stored quantity rounded to bfloat16,
the precision below the float32 the configuration keeps its weights and
moments in.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 1024   # query rows (and output-head rows) evaluated at once


def dims(cfg: dict) -> dict:
    n = int(cfg["n_routed_experts"])
    return dict(
        D=int(cfg["hidden_size"]), H=int(cfg["num_attention_heads"]),
        r=int(cfg["kv_lora_rank"]), nope=int(cfg["qk_nope_head_dim"]),
        rope=int(cfg["qk_rope_head_dim"]), dv=int(cfg["v_head_dim"]),
        F=int(cfg["intermediate_size"]), Fe=int(cfg["moe_intermediate_size"]),
        n=n, E=int(cfg.get("n_routed_experts_published", n)),
        k=int(cfg["num_experts_per_tok"]), Ns=int(cfg["n_shared_experts"]),
        Ld=int(cfg["first_k_dense_replace"]),
        Lm=int(cfg["num_hidden_layers"]) - int(cfg["first_k_dense_replace"]),
        V=int(cfg["vocab_size"]), eps=float(cfg["rms_norm_eps"]),
        theta=float(cfg["rope_theta"]), alpha=float(cfg["aux_loss_alpha"]),
        norm_topk=bool(cfg["norm_topk_prob"]))


def yarn(cfg: dict) -> tuple:
    """(inv_freq (rope/2,), cos/sin factor, softmax scale) from the
    published ``rope_scaling``: DeepSeek-V2's YaRN."""
    d = dims(cfg)
    rs = cfg["rope_scaling"]
    dim, base, f = d["rope"], d["theta"], float(rs["factor"])

    def corr(rot):
        return (dim * math.log(float(rs["original_max_position_embeddings"])
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    def mscale(m):
        return 1.0 if f <= 1 else 0.1 * m * math.log(f) + 1.0

    lo = max(math.floor(corr(float(rs["beta_fast"]))), 0)
    hi = min(math.ceil(corr(float(rs["beta_slow"]))), dim - 1)
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo)
                    / max(hi - lo, 0.001), 0.0, 1.0)
    mask = 1.0 - ramp
    inv = extra / f * (1.0 - mask) + extra * mask
    m_all = float(rs.get("mscale_all_dim", 0.0))
    cs = mscale(float(rs.get("mscale", 1.0))) / mscale(m_all)
    scale = (d["nope"] + d["rope"]) ** -0.5 * (mscale(m_all) ** 2
                                               if m_all else 1.0)
    return inv, cs, scale


def init_weights(cfg: dict, key) -> dict:
    """Random weights: projections N(0, 1/fan_in), embedding N(0, 0.02^2),
    norm scales 1 + N(0, 0.1^2).  Call under jit."""
    d = dims(cfg)
    D, H, r, nope, rope, dv = (d[k] for k in "D H r nope rope dv".split())
    F, Fe, n, E, Ns, Ld, Lm, V = (d[k] for k in
                                  "F Fe n E Ns Ld Lm V".split())
    ks = iter(jax.random.split(key, 32))

    def normal(shape, std):
        return jax.random.normal(next(ks), shape, jnp.float32) * std

    def attn(L):
        return {"q": normal((L, D, H * (nope + rope)), D ** -0.5),
                "kv_a": normal((L, D, r + rope), D ** -0.5),
                "kv_norm": 1.0 + normal((L, r), 0.1),
                "kv_b": normal((L, r, H * (nope + dv)), r ** -0.5),
                "o": normal((L, H * dv, D), (H * dv) ** -0.5)}

    def mlp(shape, width):
        return {"gate": normal(shape + (D, width), D ** -0.5),
                "up": normal(shape + (D, width), D ** -0.5),
                "down": normal(shape + (width, D), width ** -0.5)}

    def norms(L):
        return {"ln1": 1.0 + normal((L, D), 0.1),
                "ln2": 1.0 + normal((L, D), 0.1)}

    return {"embed": normal((V, D), 0.02),
            "lm_head": normal((D, V), D ** -0.5),
            "norm": 1.0 + normal((D,), 0.1),
            "dense": dict(norms(Ld), attn=attn(Ld), mlp=mlp((Ld,), F)),
            "moe": dict(norms(Lm), attn=attn(Lm),
                        router=normal((Lm, D, E), D ** -0.5),
                        experts=mlp((Lm, n), Fe),
                        shared=mlp((Lm,), Ns * Fe))}


def _round(x, mode):
    if mode == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(spec, a, b, mode):
    return jnp.einsum(spec, _round(a, mode), _round(b, mode),
                      precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, cos, sin):
    """Rotate interleaved pairs (2i, 2i+1) of the last axis by angle i."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _mlp(p, h, mode):
    g = _mm("sd,df->sf", h, p["gate"], mode)
    u = _mm("sd,df->sf", h, p["up"], mode)
    return _mm("sf,fd->sd", jax.nn.silu(g) * u, p["down"], mode)


def attention(cfg: dict, p: dict, h, mode: str = "f32"):
    """Causal latent attention of one sequence: (S, D) -> (S, D)."""
    d = dims(cfg)
    H, r, nope, rope, dv = (d[k] for k in "H r nope rope dv".split())
    S = h.shape[0]
    inv, cs, scale = yarn(cfg)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang) * cs, jnp.sin(ang) * cs          # (S, rope/2)
    q = _mm("sd,dh->sh", h, p["q"], mode).reshape(S, H, nope + rope)
    kva = _mm("sd,dh->sh", h, p["kv_a"], mode)
    c = _rms(kva[:, :r], p["kv_norm"], d["eps"])
    k_pe = _rope(kva[:, r:], cos, sin)                        # (S, rope)
    kv = _mm("sr,rh->sh", c, p["kv_b"], mode).reshape(S, H, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_pe[:, None, :], (S, H, rope))], -1)
    v = kv[..., nope:]
    q = jnp.concatenate([q[..., :nope],
                         _rope(q[..., nope:], cos[:, None], sin[:, None])],
                        -1)
    blk = min(BLOCK, S)
    assert S % blk == 0, (S, blk)

    @jax.checkpoint
    def rows(i0):
        qb = jax.lax.dynamic_slice_in_dim(q, i0, blk)
        s = _mm("qhd,khd->hqk", qb, k, mode) * scale
        seen = jnp.arange(S)[None, :] <= (i0 + jnp.arange(blk))[:, None]
        a = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return _mm("hqk,khd->qhd", a, v, mode).reshape(blk, H * dv)

    o = jax.lax.map(rows, jnp.arange(0, S, blk)).reshape(S, H * dv)
    return _mm("sh,hd->sd", o, p["o"], mode)


def experts(cfg: dict, p: dict, h, mode: str = "f32", first: int = 0):
    """The expert layer of one sequence: (S, D) -> ((S, D), balance loss).
    Each held expert runs on every token, weighted by the token's router
    probability where the expert is among its top k and by 0 elsewhere."""
    d = dims(cfg)
    S, E, k, n = h.shape[0], d["E"], d["k"], d["n"]
    probs = jax.nn.softmax(_mm("sd,de->se", h, p["router"], mode), axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    if d["norm_topk"]:
        topv = topv / jnp.sum(topv, -1, keepdims=True)
    gates = jnp.zeros((S, E), jnp.float32).at[
        jnp.arange(S)[:, None], topi].set(topv)

    @jax.checkpoint
    def expert(y, xs):
        e, pe = xs
        return y + gates[:, first + e][:, None] * _mlp(pe, h, mode), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (jnp.arange(n), p["experts"]))
    counts = jnp.sum(jax.nn.one_hot(topi, E, dtype=jnp.float32), (0, 1))
    aux = jnp.sum(counts * E / (k * S) * jnp.mean(probs, 0))
    return y + _mlp(p["shared"], h, mode), aux


def _layer(cfg, h_fn, mode, x, p):
    eps = dims(cfg)["eps"]
    x = x + attention(cfg, p["attn"], _rms(x, p["ln1"], eps), mode)
    f, aux = h_fn(p, _rms(x, p["ln2"], eps))
    return x + f, aux


def seq_loss(cfg: dict, w: dict, tokens, labels, mode: str = "f32",
             first: int = 0):
    """One sequence's loss: mean cross-entropy of ``labels`` plus alpha
    times the balance loss summed over the expert layers."""
    d = dims(cfg)
    S = tokens.shape[0]
    dense = jax.checkpoint(functools.partial(
        _layer, cfg, lambda p, h: (_mlp(p["mlp"], h, mode), 0.0), mode))
    moe = jax.checkpoint(functools.partial(
        _layer, cfg, lambda p, h: experts(cfg, p, h, mode, first), mode))
    x = jnp.take(w["embed"], tokens, axis=0)
    for i in range(d["Ld"]):
        x, _ = dense(x, jax.tree.map(lambda a: a[i], w["dense"]))
    x, aux = jax.lax.scan(moe, x, w["moe"])
    x = _rms(x, w["norm"], d["eps"])
    blk = min(BLOCK, S)

    @jax.checkpoint
    def nll(args):
        xb, lb = args
        lg = _mm("sd,dv->sv", xb, w["lm_head"], mode)
        gold = jnp.take_along_axis(lg, lb[:, None], 1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(lg, -1) - gold)

    total = jnp.sum(jax.lax.map(nll, (x.reshape(S // blk, blk, -1),
                                      labels.reshape(S // blk, blk))))
    return total / S + d["alpha"] * jnp.sum(aux)


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _seq_value_and_grad(cfg_json, w, tokens, labels, mode, first):
    cfg = json.loads(cfg_json)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(seq_loss, argnums=1)(
            cfg, w, tokens, labels, mode, first)


def loss_and_grads(cfg: dict, w: dict, tokens, labels, mode: str = "f32",
                   first: int = 0):
    """The batch's loss (the mean over its sequences of :func:`seq_loss`)
    and its gradients with respect to ``w``; tokens, labels: (B, S)."""
    key = json.dumps(cfg, sort_keys=True)
    B = tokens.shape[0]
    loss, grads = 0.0, None
    for b in range(B):
        lb, gb = _seq_value_and_grad(key, w, tokens[b], labels[b], mode,
                                     first)
        loss = loss + lb / B
        gb = jax.tree.map(lambda g: g / B, gb)
        grads = gb if grads is None else jax.tree.map(jnp.add, grads, gb)
    return loss, grads



def clip_grads(cfg: dict, grads):
    """``grads`` scaled to a global L2 norm of at most ``train.clip_norm``."""
    return _clip(float(cfg["train"]["clip_norm"]), grads)


@functools.partial(jax.jit, static_argnums=0)
def _clip(clip_norm: float, grads):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip_norm / norm)
    return jax.tree.map(lambda g: g.astype(jnp.float32) * scale, grads)


def adamw_update(cfg: dict, w, grads, count: int, mode: str = "f32"):
    """What one AdamW step adds to ``w``, from zero moments after ``count``
    steps: with ``g`` the clipped gradients (:func:`clip_grads`), ``t =
    count + 1`` and ``lr = learning_rate * min(1, t / warmup_steps)``,

        m = (1 - b1) g,  v = (1 - b2) g^2,
        w' = w - lr (m / (1 - b1^t) / (sqrt(v / (1 - b2^t)) + eps) + wd w).

    Returns ``w' - w`` leaf by leaf, float32, computed as such (not through
    ``w'``).  ``mode="bf16"`` rounds ``w``, ``g``, ``m``, ``v`` and ``w'`` to
    bfloat16 and returns ``w' - w``."""
    t = cfg["train"]
    step = count + 1
    lr = float(t["learning_rate"]) * min(1.0, step / int(t["warmup_steps"]))
    return _adamw(lr, float(t["adam_b1"]), float(t["adam_b2"]),
                  float(t["adam_eps"]), float(t["weight_decay"]), step,
                  float(t["clip_norm"]), mode == "bf16", w, grads)


@functools.partial(jax.jit, static_argnums=tuple(range(8)))
def _adamw(lr, b1, b2, eps, wd, step, clip_norm, low, w, grads):
    def rnd(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32) if low else a

    def one(p0, g):
        p0 = p0.astype(jnp.float32)
        p, g = rnd(p0), rnd(g)
        m, v = rnd((1 - b1) * g), rnd((1 - b2) * g * g)
        mhat, vhat = m / (1 - b1 ** step), v / (1 - b2 ** step)
        upd = mhat / (jnp.sqrt(vhat) + eps) + wd * p
        return rnd(p - lr * upd) - p0 if low else -lr * upd

    return jax.tree.map(one, w, _clip(clip_norm, grads))
