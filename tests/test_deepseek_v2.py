"""DeepSeek-V2(-Lite): latent attention with YaRN rope, an expert layer that
holds a share of the routed experts, and the DeepSeek-V2 balance loss,
against the plain float32 reference the chip benchmark checks the timed
path with (``benchmarks/chip/references/deepseek_v2.py``, loaded by path),
on seeded weights at a small size."""

import dataclasses
import functools
import importlib.util
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import Model, layers, moe

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"


def _load(kind: str):
    path = CHIP / kind / "deepseek_v2.py"
    spec = importlib.util.spec_from_file_location(f"dsv2_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("references")
adapter = _load("adapters")


def small_cfg(**over) -> dict:
    """The benchmark's configuration file at widths a CPU test holds: 4 of
    16 routed experts held, top 6, YaRN as published."""
    cfg = json.loads((CHIP / "configs" / "deepseek-v2-lite.json")
                     .read_text())
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
               kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, intermediate_size=128,
               moe_intermediate_size=32, n_routed_experts=4,
               n_routed_experts_published=16, num_hidden_layers=3,
               vocab_size=256)
    cfg["train"] = dict(cfg["train"], compute_dtype="float32", remat="none",
                        attn_chunk=0, loss_chunk=0)
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def batch():
    seq = np.random.default_rng(5).integers(0, 256, (2, 33)).astype(np.int32)
    return {"tokens": jnp.asarray(seq[:, :-1]),
            "labels": jnp.asarray(seq[:, 1:])}


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("attn_chunk", [0, 8])
def test_program_matches_reference(batch, attn_chunk):
    """Logits, loss and every gradient of the program (float32 compute)
    equal the reference's to rounding, with and without query-chunked
    attention."""
    cfg = small_cfg()
    cfg["train"] = dict(cfg["train"], attn_chunk=attn_chunk)
    w = jax.jit(functools.partial(ref.init_weights, cfg))(
        jax.random.PRNGKey(3))
    model = adapter.program_model(cfg)
    params = adapter.program_params(w, cfg)
    with jax.default_matmul_precision("highest"):
        logits, _ = model.forward(params, batch["tokens"])
        (loss, met), grads = jax.value_and_grad(
            model.loss_fn, has_aux=True)(params, batch)

        def ref_logits(tokens):
            d = ref.dims(cfg)
            x = jnp.take(w["embed"], tokens, axis=0)
            for i in range(d["Ld"]):
                x, _ = ref._layer(cfg, lambda p, h: (ref._mlp(
                    p["mlp"], h, "f32"), 0.0), "f32", x,
                    jax.tree.map(lambda a: a[i], w["dense"]))
            for i in range(d["Lm"]):
                x, _ = ref._layer(cfg, lambda p, h: ref.experts(
                    cfg, p, h), "f32", x,
                    jax.tree.map(lambda a: a[i], w["moe"]))
            return ref._rms(x, w["norm"], d["eps"]) @ w["lm_head"]

        want = jnp.stack([ref_logits(t) for t in batch["tokens"]])
    loss_r, grads_r = ref.loss_and_grads(cfg, w, batch["tokens"],
                                         batch["labels"])
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    assert float(loss) == pytest.approx(float(loss_r), rel=1e-5)
    assert met["held_expert_tokens"].shape == (2, 4)
    mapped = adapter.program_params(grads_r, cfg)
    assert jax.tree.structure(mapped) == jax.tree.structure(grads)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(mapped)):
        assert _rel(a, b) < 1e-4


def test_yarn_at_the_published_dims():
    """DeepSeek-V2-Lite's YaRN: correction range [10, 23] over the 32
    frequencies of the 64-wide rope part, and the softmax scale
    192^-1/2 m^2 = 0.114721 with m = 0.1 * 0.707 * ln 40 + 1."""
    cfg = get_config("deepseek-v2-lite")
    y = cfg.yarn
    inv = np.asarray(layers.yarn_inv_freq(64, cfg.rope_theta, y), np.float64)
    i = np.arange(32)
    extra = 10000.0 ** (-2 * i / 64)
    ramp = np.clip((i - 10) / (23 - 10), 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert inv[10] == pytest.approx(extra[10], rel=1e-6)   # below lo: plain
    assert inv[23] == pytest.approx(extra[23] / 40, rel=1e-6)  # above hi
    m = 0.1 * 0.707 * math.log(40) + 1
    assert layers.mla_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    assert layers.mla_scale(cfg) == pytest.approx(0.114721, abs=5e-7)
    # mscale equals mscale_all_dim, so cos and sin are not scaled
    cos, sin = layers.rope_angles(jnp.arange(5)[None], 64, cfg.rope_theta,
                                  yarn=y)
    np.testing.assert_allclose(np.asarray(cos ** 2 + sin ** 2), 1.0,
                               rtol=1e-6)
    # the reference computes the same
    inv_r, cs, scale = ref.yarn(json.loads(
        (CHIP / "configs" / "deepseek-v2-lite.json").read_text()))
    np.testing.assert_allclose(np.asarray(inv_r), inv, rtol=1e-6)
    assert cs == 1.0 and scale == pytest.approx(layers.mla_scale(cfg))


def test_eight_shares_sum_to_the_whole_layer():
    """Eight chips' shares of an expert layer of 16 experts, 2 held on
    each, with what every chip computes alike (the shared experts) counted
    once, add up to the reference's uncut layer."""
    whole = small_cfg(n_routed_experts=16)
    w = jax.jit(functools.partial(ref.init_weights, whole))(
        jax.random.PRNGKey(7))
    p = jax.tree.map(lambda a: a[0], w["moe"])
    h = jax.random.normal(jax.random.PRNGKey(8), (2, 32, 64))
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.experts(whole, p, x)[0] for x in h])
        share = small_cfg(n_routed_experts=2)
        cfg = adapter.program_model(share).cfg
        shared = layers.mlp(p["shared"], cfg, h, act_fn="swiglu")
        total = -7 * shared
        counts = 0
        for s in range(8):
            held = dict(router=p["router"], shared=p["shared"],
                        experts=jax.tree.map(lambda a: a[2 * s:2 * s + 2],
                                             p["experts"]))
            y, _, n = moe.moe_apply_grouped(held, cfg, h, first_expert=2 * s)
            # the reference given the same share computes the same part
            part = jnp.stack([ref.experts(share, dict(p, experts=held[
                "experts"]), x, first=2 * s)[0] for x in h])
            np.testing.assert_allclose(np.asarray(y), np.asarray(part),
                                       rtol=1e-4, atol=1e-5)
            total = total + y
            counts = counts + int(jnp.sum(n["held_expert_tokens"]))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert counts == 2 * 32 * 6          # every token's 6 pairs, once


def test_deepseek_moe_16b_all_experts_held_unchanged(key):
    """deepseek-moe-16b with every expert held goes through the held
    (dropless) path and gives what its capacity path gives where that one
    drops nothing: forward and gradients."""
    cfg = get_config("deepseek-moe-16b", reduced=True)
    assert cfg.moe.n_held == 0
    held = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, n_held=cfg.moe.n_experts))
    m1, m2 = Model(cfg), Model(held)
    params = m1.init(key)
    toks = jax.random.randint(key, (2, 24), 0, cfg.vocab)
    l1, _ = jax.jit(m1.forward)(params, toks)
    l2, _ = jax.jit(m2.forward)(params, toks)
    assert float(jnp.max(jnp.abs(l1 - l2))) < 1e-4
    b = {"tokens": toks, "labels": toks}
    g1 = jax.grad(lambda p: m1.loss_fn(p, b, aux_weight=0.0)[0])(params)
    g2 = jax.grad(lambda p: m2.loss_fn(p, b, aux_weight=0.0)[0])(params)
    d = max(float(jnp.max(jnp.abs(x - y))) for x, y in zip(
        jax.tree.leaves(g1), jax.tree.leaves(g2)))
    assert d < 1e-4, f"held path grads diverge: {d}"


def test_held_path_is_chunked_over_tokens(monkeypatch):
    """Tokens go through the held experts in chunks; the result and the
    counts do not depend on the chunk."""
    cfg = adapter.program_model(small_cfg()).cfg
    w = jax.jit(functools.partial(ref.init_weights, small_cfg()))(
        jax.random.PRNGKey(1))
    p = jax.tree.map(lambda a: a[0], w["moe"])
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 64))
    y1, a1, n1 = moe.moe_apply_grouped(p, cfg, h)
    monkeypatch.setattr(moe, "GROUP_CHUNK", 16)
    y4, a4, n4 = moe.moe_apply_grouped(p, cfg, h)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y4), rtol=1e-5,
                               atol=1e-6)
    assert float(a1) == pytest.approx(float(a4))
    np.testing.assert_array_equal(np.asarray(n1["held_expert_tokens"]),
                                  np.asarray(n4["held_expert_tokens"]))


@pytest.mark.parametrize("seq_aux", [False, True])
def test_every_path_takes_the_same_balance_loss(key, seq_aux):
    """The capacity path (dropless here) and the grouped path with every
    expert held take one balance loss, the per-sequence one where the
    config asks for it."""
    cfg = get_config("deepseek-moe-16b", reduced=True)
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, seq_aux=seq_aux))
    grouped = dataclasses.replace(cfg, moe_ragged=True)
    p = moe.moe_init(key, cfg, jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 24, cfg.d_model))
    y1, a1, n1 = moe.moe_apply(p, cfg, h)
    y2, a2, n2 = moe.moe_apply(p, grouped, h)
    assert n1 == {} and n2 == {}
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4,
                               atol=1e-5)
    assert float(a1) == pytest.approx(float(a2), rel=1e-6)
    logits = jnp.einsum("bsd,de->bse", h, p["router"])
    topi = jax.lax.top_k(jax.nn.softmax(logits, -1), cfg.moe.top_k)[1]
    other = moe.balance_loss(dataclasses.replace(cfg.moe,
                                                 seq_aux=not seq_aux),
                             jax.nn.softmax(logits, -1), topi)
    assert float(a1) != pytest.approx(float(other), rel=1e-3)


def test_grouped_path_pallas_kernel_equals_ragged_dot():
    """The grouped matmul is the path's one point of choice: the Pallas
    kernel (``use_pallas``) computes what ``lax.ragged_dot`` does, held
    share and all."""
    cfg = adapter.program_model(small_cfg()).cfg
    w = jax.jit(functools.partial(ref.init_weights, small_cfg()))(
        jax.random.PRNGKey(4))
    p = jax.tree.map(lambda a: a[0], w["moe"])
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 16, 64))
    y1, a1, n1 = moe.moe_apply_grouped(p, cfg, h, first_expert=1)
    y2, a2, n2 = moe.moe_apply_grouped(
        p, dataclasses.replace(cfg, use_pallas=True), h, first_expert=1)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(n1["held_expert_tokens"]),
                                  np.asarray(n2["held_expert_tokens"]))
    assert float(a1) == float(a2)


@pytest.mark.parametrize("count", [1000, 2000])
def test_reference_adamw_step_is_the_programs(count):
    """The reference's AdamW step (float32, jax.numpy) adds to the weights
    what the program's ``AdamW.update`` does from zero moments after
    ``count`` steps, clipping included; in bfloat16 it does not."""
    from repro.train import AdamW
    from repro.train.optimizer import linear_warmup
    t = small_cfg()["train"]
    opt = AdamW(lr=linear_warmup(t["learning_rate"], t["warmup_steps"]),
                b1=t["adam_b1"], b2=t["adam_b2"], eps=t["adam_eps"],
                weight_decay=t["weight_decay"], clip_norm=t["clip_norm"])
    rng = np.random.default_rng(count)
    w = {"a": (1 + 0.1 * rng.standard_normal(64)).astype(np.float32),
         "b": (0.05 * rng.standard_normal((32, 48))).astype(np.float32)}
    g = {"a": rng.standard_normal(64).astype(np.float32),
         "b": 1e-3 * rng.standard_normal((32, 48)).astype(np.float32)}
    state = dict(opt.init(w), step=jnp.asarray(count, jnp.int32))
    new, _, stats = opt.update(g, state, w)
    assert float(stats["grad_norm"]) > t["clip_norm"]     # clipping acts
    want = ref.adamw_update(small_cfg(), w, g, count)
    low = ref.adamw_update(small_cfg(), w, g, count, "bf16")
    for k in w:
        got = np.asarray(new[k], np.float64) - w[k]
        ref_k = np.asarray(want[k], np.float64)
        err = np.linalg.norm(got - ref_k) / np.linalg.norm(ref_k)
        assert err < 2e-3, (k, err)
    assert np.linalg.norm(np.asarray(low["a"]) - np.asarray(want["a"])) \
        > 0.1 * np.linalg.norm(np.asarray(want["a"]))
