"""Plain float32 reference of the Qwen2 decoder, and the benchmark's weights.

Follows the published description (arXiv:2407.10671; the Hugging Face
``Qwen2ForCausalLM``): token embedding; per layer RMSNorm, grouped-query
attention with biased q/k/v projections and rotary position embedding
(half-split layout, ``rope_theta``), a residual add, RMSNorm, the SiLU-gated
MLP and a residual add; a final RMSNorm and the output head (the embedding,
transposed, where ``tie_word_embeddings``).  Qwen2-VL's text model is the same
stack; for text tokens its M-RoPE gives all three position streams the same
index, which is exactly this rotary embedding.

No cache, no batching, no kernels: one sequence at a time, every matrix
product in float32 at the highest precision.  ``mode="fp8"`` is the control:
the same computation with each product's operands rounded to float8 e4m3,
the precision below the bfloat16 the model is served in.

Weights are random from a seed, made on the device in one jitted call in
float32, the type the program holds them in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 256   # rows of logits computed at once
PAD = 1024    # sequences are padded to a multiple: few programs to compile


def dims(cfg: dict) -> dict:
    D, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return dict(D=D, H=H, K=int(cfg["num_key_value_heads"]),
                hd=int(cfg.get("head_dim") or D // H),
                F=int(cfg["intermediate_size"]),
                L=int(cfg["num_hidden_layers"]), V=int(cfg["vocab_size"]),
                eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]),
                tied=bool(cfg["tie_word_embeddings"]))


def init_weights(cfg: dict, key) -> dict:
    """Random weights: projections N(0, 1/fan_in), embedding N(0, 0.02^2),
    biases N(0, 0.02^2), norm scales 1 + N(0, 0.1^2).  Call under jit."""
    d = dims(cfg)
    D, H, K, hd, F, L, V = (d[k] for k in "D H K hd F L V".split())
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return jax.random.normal(next(ks), shape, jnp.float32) * std

    w = {"embed": normal((V, D), 0.02),
         "norm": 1.0 + normal((D,), 0.1),
         "layers": {
             "ln1": 1.0 + normal((L, D), 0.1),
             "ln2": 1.0 + normal((L, D), 0.1),
             "q_w": normal((L, D, H * hd), D ** -0.5),
             "k_w": normal((L, D, K * hd), D ** -0.5),
             "v_w": normal((L, D, K * hd), D ** -0.5),
             "o_w": normal((L, H * hd, D), (H * hd) ** -0.5),
             "q_b": normal((L, H * hd), 0.02),
             "k_b": normal((L, K * hd), 0.02),
             "v_b": normal((L, K * hd), 0.02),
             "gate": normal((L, D, F), D ** -0.5),
             "up": normal((L, D, F), D ** -0.5),
             "down": normal((L, F, D), F ** -0.5)}}
    if not d["tied"]:
        w["lm_head"] = normal((D, V), D ** -0.5)
    return w


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


def _rope(x, pos, theta):
    """x: (S, n, hd); rotate the two halves of the head dimension."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]        # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden(cfg: dict, w: dict, tokens, mode: str = "f32"):
    """Final-norm hidden states of one sequence: (S,) -> (S, D)."""
    d = dims(cfg)
    H, K, hd, eps = d["H"], d["K"], d["hd"], d["eps"]
    S = tokens.shape[0]
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]

    def layer(x, p):
        h = _rms(x, p["ln1"], eps)
        q = (_mm("sd,dh->sh", h, p["q_w"], mode) + p["q_b"]).reshape(S, H, hd)
        k = (_mm("sd,dh->sh", h, p["k_w"], mode) + p["k_b"]).reshape(S, K, hd)
        v = (_mm("sd,dh->sh", h, p["v_w"], mode) + p["v_b"]).reshape(S, K, hd)
        q, k = _rope(q, pos, d["theta"]), _rope(k, pos, d["theta"])
        g = H // K
        qg = q.reshape(S, K, g, hd)
        s = _mm("skgd,tkd->kgst", qg, k, mode) * hd ** -0.5
        s = jnp.where(causal[None, None], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = _mm("kgst,tkd->skgd", a, v, mode).reshape(S, H * hd)
        x = x + _mm("sh,hd->sd", o, p["o_w"], mode)
        h = _rms(x, p["ln2"], eps)
        f = jax.nn.silu(_mm("sd,df->sf", h, p["gate"], mode)) \
            * _mm("sd,df->sf", h, p["up"], mode)
        return x + _mm("sf,fd->sd", f, p["down"], mode), None

    x = jnp.take(w["embed"], tokens, axis=0)
    x, _ = jax.lax.scan(layer, x, w["layers"])
    return _rms(x, w["norm"], eps)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _stats(cfg_items, w, tokens, targets, mode):
    cfg = dict(cfg_items)
    x = hidden(cfg, w, tokens, mode)
    head = w["embed"].T if cfg["tie_word_embeddings"] else w["lm_head"]
    S = x.shape[0]

    def block(args):
        xb, tb = args
        lg = _mm("sd,dv->sv", xb, head, mode)
        return (jnp.max(lg, -1), jnp.take_along_axis(lg, tb[:, None], 1)[:, 0],
                jnp.argmax(lg, -1).astype(jnp.int32))

    out = jax.lax.map(block, (x.reshape(S // BLOCK, BLOCK, -1),
                              targets.reshape(S // BLOCK, BLOCK)))
    return tuple(o.reshape(S) for o in out)


def padded(n: int) -> int:
    return -(-int(n) // PAD) * PAD


def logit_stats(cfg: dict, w: dict, tokens, targets, mode: str = "f32"):
    """Per position of one sequence: the largest logit, the logit of
    ``targets[i]``, and the token with the largest logit.  The sequence is
    padded to a multiple of ``PAD`` (causal, so padding changes nothing
    before it); results are cut back to its length."""
    import numpy as np
    S = len(tokens)
    Sp = padded(S)
    tk = np.zeros(Sp, np.int32)
    tg = np.zeros(Sp, np.int32)
    tk[:S], tg[:S] = tokens, targets
    key = tuple(sorted((k, v) for k, v in cfg.items()
                       if isinstance(v, (int, float, str, bool))))
    mx, at, am = _stats(key, w, jnp.asarray(tk), jnp.asarray(tg), mode)
    return (np.asarray(mx)[:S], np.asarray(at)[:S], np.asarray(am)[:S])
