"""Plain float32 reference of Nemotron-H training (``model_type``
``nemotron_h``; NVIDIA-Nemotron-3-Nano-30B-A3B): forward, loss and (by
``jax.grad``) gradients, and the benchmark's weights.

Follows the published description (the Hugging Face ``NemotronHForCausalLM``
and the Mamba-2 paper, arXiv:2405.21060) in its published form.  Layer ``i``
has one mixer, the ``i``-th letter of ``hybrid_override_pattern``: M a
Mamba-2 mixer, E an expert layer, * attention; each block is ``x +
mixer(RMSNorm(x))``.

* M: ``[z, xBC, dt] = h W_in``; ``xBC`` through a causal depthwise conv of
  ``conv_kernel`` with bias and SiLU, split into ``x`` (``mamba_num_heads``
  heads of ``mamba_head_dim``), ``B`` and ``C`` (``n_groups`` groups of
  ``ssm_state_size``; head ``h`` reads group ``h // (heads / n_groups)``);
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``.  The SSM in its
  quadratic (dual) form, ``y_t = sum_{s<=t} L_ts (C_t . B_s) dt_s x_s +
  D x_t`` with ``L_ts = exp(sum_{s<r<=t} dt_r A)``: each exponent is a sum
  over the positions between ``s`` and ``t``, not a difference of running
  sums.  Then the gated RMSNorm ``norm(y * silu(z))`` over each group of
  ``heads * head_dim / n_groups`` channels, and ``W_out``.
* E: sigmoid scores ``s = sigmoid(h W_r)`` in float32 over every routed
  expert; the top ``k`` of ``s + router_bias`` chosen; their weights the
  chosen scores, renormalised (``norm_topk_prob``, ``+ 1e-20``), times
  ``routed_scaling_factor``.  Experts and the shared expert are relu^2
  MLPs, ``down(relu(up h)^2)``.  The balance loss of each sequence is
  DeepSeek-V3's sequence-wise loss ``sum_i f_i P_i`` (``f_i = E/(k S)
  #{t : i chosen}``, ``P_i`` the mean of the scores normalised over the
  experts), weighted by ``aux_loss_alpha`` and summed over expert layers.
* *: grouped-query attention, causal softmax at ``head_dim^-1/2``, no
  position encoding, no bias.

A final RMSNorm and the untied head; the loss is the mean next-token
cross-entropy plus the balance term.  The correction bias takes no
gradient; its step (:func:`bias_update`) is taken from the program's
counts of the tokens that chose each expert, as the AdamW step is taken
from the program's gradient.

Departures, each shared with the program under test:

* held experts: the configuration's ``n_routed_experts`` experts are held,
  experts ``first .. first + n_routed_experts - 1`` of the
  ``n_routed_experts_published`` the router scores; the part the other
  experts would add is left out (one chip's share of expert parallelism);
* ``vocab_size`` may be a slice of the published vocabulary, and the first
  ``num_hidden_layers`` letters of the pattern are the layers kept;
* no dropout.

Every matrix product is float32 at the highest precision.  There are no
kernels, no cache and no batching: one sequence at a time, each layer under
``jax.checkpoint`` and each block of query rows (of ``SSM_BLOCK`` for the
SSM, ``ATTN_BLOCK`` for attention, ``BLOCK`` for the head) under its own;
the arithmetic is the same.  ``mode="fp8"`` is the control: every product's
operands rounded to float8 e4m3, the precision below the bfloat16 the
configuration computes in.

Weights are random from a seed, made on the device in one jitted call in
float32, the type the program holds them in.  The optimizer step
(:func:`adamw_update`, with :func:`clip_grads`) is AdamW with decoupled
weight decay after clipping the gradients to a global L2 norm, at the
learning rate of a linear warmup, from the file's ``train`` keys, in
float32 on any tree of arrays, leaving each ``router_bias`` leaf as it is;
``mode="bf16"`` is its control, every stored quantity rounded to bfloat16.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 1024       # output-head rows evaluated at once
ATTN_BLOCK = 256   # attention query rows evaluated at once
SSM_BLOCK = 128    # SSM query rows evaluated at once
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


def dims(cfg: dict) -> dict:
    n = int(cfg["n_routed_experts"])
    L = int(cfg["num_hidden_layers"])
    H = int(cfg["mamba_num_heads"])
    P = int(cfg["mamba_head_dim"])
    G, N = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    return dict(
        D=int(cfg["hidden_size"]), V=int(cfg["vocab_size"]),
        pattern=cfg["hybrid_override_pattern"][:L],
        Hm=H, P=P, G=G, N=N, di=H * P, conv=H * P + 2 * G * N,
        k_conv=int(cfg["conv_kernel"]),
        Ha=int(cfg["num_attention_heads"]),
        Hkv=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        n=n, E=int(cfg.get("n_routed_experts_published", n)),
        k=int(cfg["num_experts_per_tok"]),
        Fe=int(cfg["moe_intermediate_size"]),
        Fs=int(cfg["moe_shared_expert_intermediate_size"]),
        scale=float(cfg["routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        eps=float(cfg["norm_eps"]), alpha=float(cfg["aux_loss_alpha"]),
        rate=float(cfg["bias_update_rate"]))


def kinds(cfg: dict) -> list:
    """The mixer of each layer kept: ``mamba``, ``moe`` or ``attention``."""
    return [KINDS[c] for c in dims(cfg)["pattern"]]


def init_weights(cfg: dict, key) -> dict:
    """Random weights: projections N(0, 1/fan_in), embedding N(0, 0.02^2),
    norm scales 1 + N(0, 0.1^2), the conv's weight and bias U(+-k^-1/2)
    (PyTorch's default for a depthwise conv of width k); Mamba-2's
    published ranges for ``A`` (U(1, 16)), ``dt`` (log-uniform over
    ``time_step_min`` .. ``time_step_max``, floored at
    ``time_step_floor``, stored as softplus^-1 in ``dt_bias``) and ``D``
    (ones); the correction bias zeros.  Call under jit."""
    d = dims(cfg)
    D, V, Hm, di, conv, kc = (d[x] for x in "D V Hm di conv k_conv".split())
    ks = iter(jax.random.split(key, 8 + 12 * len(d["pattern"])))

    def normal(shape, std):
        return jax.random.normal(next(ks), shape, jnp.float32) * std

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(ks), shape, jnp.float32, lo, hi)

    def norm(width):
        return 1.0 + normal((width,), 0.1)

    def mlp(shape, width):
        return {"up": normal(shape + (D, width), D ** -0.5),
                "down": normal(shape + (width, D), width ** -0.5)}

    def mamba():
        lo, hi = (math.log(float(cfg[k]))
                  for k in ("time_step_min", "time_step_max"))
        dt = jnp.maximum(jnp.exp(uniform((Hm,), lo, hi)),
                         float(cfg["time_step_floor"]))
        b = kc ** -0.5
        return {"ln": norm(D),
                "in_proj": normal((D, di + conv + Hm), D ** -0.5),
                "conv_w": uniform((kc, conv), -b, b),
                "conv_b": uniform((conv,), -b, b),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(uniform((Hm,), 1.0, 16.0)),
                "D": jnp.ones((Hm,), jnp.float32),
                "norm": norm(di),
                "out_proj": normal((di, D), di ** -0.5)}

    def moe():
        return {"ln": norm(D), "router": normal((D, d["E"]), D ** -0.5),
                "router_bias": jnp.zeros((d["E"],), jnp.float32),
                "experts": mlp((d["n"],), d["Fe"]),
                "shared": mlp((), d["Fs"])}

    def attention():
        q, kv = d["Ha"] * d["hd"], d["Hkv"] * d["hd"]
        return {"ln": norm(D), "q": normal((D, q), D ** -0.5),
                "k": normal((D, kv), D ** -0.5),
                "v": normal((D, kv), D ** -0.5),
                "o": normal((q, D), q ** -0.5)}

    make = {"mamba": mamba, "moe": moe, "attention": attention}
    return {"embed": normal((V, D), 0.02),
            "lm_head": normal((D, V), D ** -0.5), "norm": norm(D),
            "layers": [make[kind]() for kind in kinds(cfg)]}


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


def _relu2_mlp(p, h, mode):
    u = _mm("sd,df->sf", h, p["up"], mode)
    return _mm("sf,fd->sd", jnp.square(jax.nn.relu(u)), p["down"], mode)


def ssm(xs, dt, A, B, C, mode: str = "f32"):
    """The SSM of one sequence in its quadratic form: xs (S, H, P), dt
    (S, H), A (H,), B, C (S, G, N) -> y (S, H, P), without the D term."""
    S, H, P = xs.shape
    G = B.shape[1]
    R = H // G
    a = dt * A                                              # (S, H)
    u = (dt[:, :, None] * xs).reshape(S, G, R, P)
    blk = min(SSM_BLOCK, S)
    assert S % blk == 0, (S, blk)
    s_at = jnp.arange(S)

    @jax.checkpoint
    def rows(i0):
        t_at = i0 + jnp.arange(blk)
        a_blk = jax.lax.dynamic_slice_in_dim(a, i0, blk)    # (blk, H)
        # the sum over (s, t] for s before the block: (s, i0) then [i0, t]
        before = jnp.where((s_at < i0)[:, None], a, 0.0)
        tail = jnp.cumsum(before[::-1], 0)[::-1]            # sum over [s, i0)
        tail = jnp.concatenate([tail[1:], jnp.zeros((1, H))])  # over (s, i0)
        head = jnp.cumsum(a_blk, 0)                          # over [i0, t]
        seg = head.T[:, :, None] + tail.T[:, None, :]        # (H, blk, S)
        # for s inside the block, the sum over (s, t] directly
        r = jnp.arange(blk)
        inside = jnp.cumsum(jnp.where((r[:, None] > r[None, :])[..., None],
                                      a_blk[:, None, :], 0.0), 0)
        inside = jax.lax.dynamic_update_slice(
            jnp.zeros((H, blk, S)), jnp.moveaxis(inside, 2, 0), (0, 0, i0))
        seg = jnp.where((s_at >= i0)[None, None, :], inside, seg)
        seen = s_at[None, None, :] <= t_at[None, :, None]
        Lmat = jnp.exp(jnp.where(seen, seg, -jnp.inf))       # (H, blk, S)
        Cb = jax.lax.dynamic_slice_in_dim(C, i0, blk)
        CB = _mm("tgn,sgn->gts", Cb, B, mode)                # (G, blk, S)
        W = Lmat.reshape(G, R, blk, S) * CB[:, None]
        return _mm("grts,sgrp->tgrp", W, u, mode).reshape(blk, H, P)

    return jax.lax.map(rows, jnp.arange(0, S, blk)).reshape(S, H, P)


def mamba(cfg: dict, p: dict, h, mode: str = "f32"):
    """The Mamba-2 mixer of one sequence: (S, D) -> (S, D)."""
    d = dims(cfg)
    S = h.shape[0]
    H, P, G, N, di, conv, k = (d[x] for x in "Hm P G N di conv k_conv"
                                .split())
    proj = _mm("sd,dk->sk", h, p["in_proj"], mode)
    z, xbc, dt = proj[:, :di], proj[:, di:di + conv], proj[:, di + conv:]
    pad = jnp.concatenate([jnp.zeros((k - 1, conv)), xbc])
    xbc = jax.nn.silu(sum(pad[j:j + S] * p["conv_w"][j] for j in range(k))
                      + p["conv_b"])
    xs = xbc[:, :di].reshape(S, H, P)
    B = xbc[:, di:di + G * N].reshape(S, G, N)
    C = xbc[:, di + G * N:].reshape(S, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssm(xs, dt, -jnp.exp(p["A_log"]), B, C, mode)
    y = (y + p["D"][:, None] * xs).reshape(S, di)
    g = (y * jax.nn.silu(z)).reshape(S, G, di // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + d["eps"])
    return _mm("sk,kd->sd", g.reshape(S, di) * p["norm"], p["out_proj"],
               mode)


def attention(cfg: dict, p: dict, h, mode: str = "f32"):
    """Causal grouped-query attention of one sequence, no position
    encoding: (S, D) -> (S, D)."""
    d = dims(cfg)
    H, K, hd = d["Ha"], d["Hkv"], d["hd"]
    S = h.shape[0]
    q = _mm("sd,dh->sh", h, p["q"], mode).reshape(S, K, H // K, hd)
    k = _mm("sd,dh->sh", h, p["k"], mode).reshape(S, K, hd)
    v = _mm("sd,dh->sh", h, p["v"], mode).reshape(S, K, hd)
    blk = min(ATTN_BLOCK, S)
    assert S % blk == 0, (S, blk)

    @jax.checkpoint
    def rows(i0):
        qb = jax.lax.dynamic_slice_in_dim(q, i0, blk)
        s = _mm("qkgd,skd->kgqs", qb, k, mode) * hd ** -0.5
        seen = jnp.arange(S)[None, :] <= (i0 + jnp.arange(blk))[:, None]
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _mm("kgqs,skd->qkgd", a, v, mode).reshape(blk, H * hd)

    o = jax.lax.map(rows, jnp.arange(0, S, blk)).reshape(S, H * hd)
    return _mm("sh,hd->sd", o, p["o"], mode)


def experts(cfg: dict, p: dict, h, mode: str = "f32", first: int = 0):
    """The expert layer of one sequence: (S, D) -> ((S, D), balance loss,
    tokens that chose each expert (E,)).  Each held expert runs on every
    token, weighted by the token's weight where the expert is chosen and by
    0 elsewhere."""
    d = dims(cfg)
    S, E, k, n = h.shape[0], d["E"], d["k"], d["n"]
    scores = jax.nn.sigmoid(_mm("sd,de->se", h, p["router"], mode))
    _, topi = jax.lax.top_k(scores + p["router_bias"], k)
    topv = jnp.take_along_axis(scores, topi, -1)
    if d["norm_topk"]:
        topv = topv / (jnp.sum(topv, -1, keepdims=True) + 1e-20)
    gates = jnp.zeros((S, E), jnp.float32).at[
        jnp.arange(S)[:, None], topi].set(topv * d["scale"])

    @jax.checkpoint
    def expert(y, xs):
        e, pe = xs
        return y + gates[:, first + e][:, None] * _relu2_mlp(pe, h, mode), \
            None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (jnp.arange(n), p["experts"]))
    counts = jnp.sum(jax.nn.one_hot(topi, E, dtype=jnp.float32), (0, 1))
    probs = scores / jnp.sum(scores, -1, keepdims=True)
    aux = jnp.sum(counts * E / (k * S) * jnp.mean(probs, 0))
    return y + _relu2_mlp(p["shared"], h, mode), aux, counts


def _layer(cfg, kind, mode, first, x, p):
    h = _rms(x, p["ln"], dims(cfg)["eps"])
    if kind == "moe":
        f, aux, _ = experts(cfg, p, h, mode, first)
        return x + f, aux
    f = (mamba if kind == "mamba" else attention)(cfg, p, h, mode)
    return x + f, 0.0


def seq_loss(cfg: dict, w: dict, tokens, labels, mode: str = "f32",
             first: int = 0):
    """One sequence's loss: mean cross-entropy of ``labels`` plus alpha
    times the balance loss summed over the expert layers."""
    d = dims(cfg)
    S = tokens.shape[0]
    x = jnp.take(w["embed"], tokens, axis=0)
    aux = 0.0
    for kind, p in zip(kinds(cfg), w["layers"]):
        x, a = jax.checkpoint(functools.partial(
            _layer, cfg, kind, mode, first))(x, p)
        aux = aux + a
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
    return total / S + d["alpha"] * aux


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


def bias_update(cfg: dict, bias, load):
    """A correction bias after one step: ``bias + bias_update_rate *
    sign(mean(load) - load)``, with ``load`` the step's tokens that chose
    each expert (the program's counts)."""
    load = jnp.asarray(load, jnp.float32)
    return bias + dims(cfg)["rate"] * jnp.sign(
        jnp.mean(load, -1, keepdims=True) - load)


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
    ``w'``), and zeros for each ``router_bias`` leaf, which AdamW does not
    move.  ``mode="bf16"`` rounds ``w``, ``g``, ``m``, ``v`` and ``w'`` to
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

    def one(path, p0, g):
        if getattr(path[-1], "key", None) == "router_bias":
            return jnp.zeros(p0.shape, jnp.float32)
        p0 = p0.astype(jnp.float32)
        p, g = rnd(p0), rnd(g)
        m, v = rnd((1 - b1) * g), rnd((1 - b2) * g * g)
        mhat, vhat = m / (1 - b1 ** step), v / (1 - b2 ** step)
        upd = mhat / (jnp.sqrt(vhat) + eps) + wd * p
        return rnd(p - lr * upd) - p0 if low else -lr * upd

    return jax.tree_util.tree_map_with_path(one, w, _clip(clip_norm, grads))
