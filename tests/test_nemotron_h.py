"""Nemotron-H (NVIDIA-Nemotron-3-Nano): Mamba-2 mixers with state groups,
sigmoid-routed relu^2 experts with a correction bias, and attention without
position encoding, one mixer per layer from a pattern, against the plain
float32 reference the chip benchmark checks the timed path with
(``benchmarks/chip/references/nemotron_h.py``, loaded by path), on seeded
weights at a small size."""

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.ssd_scan import ref as ssd_ref
from repro.models import layers, moe
from repro.models.transformer import structure
from repro.train import AdamW, train
from repro.train.train_loop import make_train_step

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"


def _load(kind: str):
    path = CHIP / kind / "nemotron_h.py"
    spec = importlib.util.spec_from_file_location(f"nemh_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("references")
adapter = _load("adapters")


def small_cfg(**over) -> dict:
    """The benchmark's configuration file at widths a CPU test holds: the
    seven layers MEMEM*E, 4 of 16 routed experts held, top 6, 8 Mamba
    heads in 2 state groups."""
    cfg = json.loads((CHIP / "configs" / "nemotron-3-nano-30b-a3b.json")
                     .read_text())
    cfg.update(hidden_size=64, mamba_num_heads=8, mamba_head_dim=8,
               n_groups=2, ssm_state_size=8, chunk_size=8,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               moe_intermediate_size=32,
               moe_shared_expert_intermediate_size=64, n_routed_experts=4,
               n_routed_experts_published=16, vocab_size=256)
    cfg["train"] = dict(cfg["train"], compute_dtype="float32", remat="none",
                        attn_chunk=0, loss_chunk=0)
    cfg.update(over)
    return cfg


def _weights(cfg, seed):
    """Reference weights with a nonzero correction bias and larger steps,
    so that the bias and the decay both matter."""
    w = jax.jit(functools.partial(ref.init_weights, cfg))(
        jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 16))
    for layer in w["layers"]:
        if "router_bias" in layer:
            layer["router_bias"] = 0.05 * jax.random.normal(
                next(keys), layer["router_bias"].shape)
        if "dt_bias" in layer:
            layer["dt_bias"] = layer["dt_bias"] + 3.0
    return w


@pytest.fixture(scope="module")
def batch():
    seq = np.random.default_rng(5).integers(0, 256, (2, 33)).astype(np.int32)
    return {"tokens": jnp.asarray(seq[:, :-1]),
            "labels": jnp.asarray(seq[:, 1:])}


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.mark.parametrize("attn_chunk, pattern, segments", [
    (0, None, ["mamba", "moe", "mamba", "moe", "mamba", "attn_only", "moe"]),
    (8, None, ["mamba", "moe", "mamba", "moe", "mamba", "attn_only", "moe"]),
    (0, "MME*EE*", ["mamba", "moe", "attn_only", "moe", "attn_only"])])
def test_program_matches_reference(batch, attn_chunk, pattern, segments):
    """Loss and every gradient of the program (float32 compute) equal the
    reference's to rounding, for a stack that holds all three mixers, with
    and without query-chunked attention, and where runs of one mixer are
    stacked into a segment; the counters have their shapes."""
    cfg = small_cfg()
    cfg["train"] = dict(cfg["train"], attn_chunk=attn_chunk)
    if pattern:
        cfg["hybrid_override_pattern"] = pattern
    model = adapter.program_model(cfg)
    assert [k for k, _ in structure(model.cfg)] == segments
    w = _weights(cfg, 3)
    params = adapter.program_params(w, cfg)
    with jax.default_matmul_precision("highest"):
        (loss, met), grads = jax.value_and_grad(
            model.loss_fn, has_aux=True)(params, batch)
    loss_r, grads_r = ref.loss_and_grads(cfg, w, batch["tokens"],
                                         batch["labels"])
    assert float(loss) == pytest.approx(float(loss_r), rel=1e-5)
    assert met["held_expert_tokens"].shape == (3, 4)
    assert met["expert_load"].shape == (3, 16)
    assert int(jnp.sum(met["expert_load"])) == 3 * 2 * 32 * 6
    mapped = adapter.program_params(grads_r, cfg)
    assert jax.tree.structure(mapped) == jax.tree.structure(grads)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(mapped)):
        assert _rel(a, b) < 1e-4, jax.tree_util.keystr(path)
    # the correction bias takes no gradient, in either
    assert float(jnp.max(jnp.abs(
        grads["segments"][1]["moe"]["router_bias"]))) == 0.0
    back = adapter.reference_weights(params, cfg)
    assert jax.tree.structure(back) == jax.tree.structure(w)


def test_reference_ssm_equals_the_recurrence():
    """The reference's quadratic form (exponents summed over the positions
    between) equals the per-step recurrence ``ssd_naive`` on every head,
    across its blocks of query rows, with two state groups."""
    rng = np.random.default_rng(0)
    S, H, P, G, N = 64, 4, 4, 2, 8
    xs = jnp.asarray(rng.normal(size=(S, H, P)), jnp.float32)
    dt = jnp.asarray(rng.random((S, H)) * 0.5, jnp.float32)
    A = -jnp.asarray(rng.random(H) * 4 + 0.5, jnp.float32)
    B = jnp.asarray(rng.normal(size=(S, G, N)) * 0.3, jnp.float32)
    C = jnp.asarray(rng.normal(size=(S, G, N)) * 0.3, jnp.float32)
    old = ref.SSM_BLOCK
    ref.SSM_BLOCK = 16
    try:
        with jax.default_matmul_precision("highest"):
            y = ref.ssm(xs, dt, A, B, C)
    finally:
        ref.SSM_BLOCK = old
    rep = lambda m: jnp.moveaxis(jnp.repeat(m, H // G, axis=1), 1, 0)  # noqa
    y0, _ = ssd_ref.ssd_naive(jnp.moveaxis(xs, 1, 0), dt.T, A, rep(B),
                              rep(C))
    np.testing.assert_allclose(np.asarray(y), np.moveaxis(np.asarray(y0),
                                                           0, 1),
                               rtol=1e-4, atol=1e-5)


def test_sixteen_shares_sum_to_the_whole_layer():
    """Sixteen chips' shares of an expert layer of 32 experts, 2 held on
    each, under the sigmoid router with its correction bias, with what
    every chip computes alike (the shared expert) counted once, add up to
    the reference's uncut layer."""
    whole = small_cfg(n_routed_experts=32, n_routed_experts_published=32)
    w = _weights(whole, 7)
    p = w["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(8), (2, 32, 64))
    share = small_cfg(n_routed_experts=2, n_routed_experts_published=32)
    cfg = adapter.program_model(share).cfg
    prog = adapter.program_params(dict(w, layers=[
        dict(layer, experts=jax.tree.map(lambda a: a[:2], layer["experts"]))
        if "experts" in layer else layer for layer in w["layers"]]), share)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.experts(whole, p, x)[0] for x in h])
        pm = jax.tree.map(lambda a: a[0], prog["segments"][1]["moe"])
        shared = layers.mlp(pm["shared"], cfg, h, act_fn="relu2")
        total = -15 * shared
        counts = 0
        for s in range(16):
            held = dict(pm, experts=jax.tree.map(
                lambda a: a[2 * s:2 * s + 2], p["experts"]))
            y, _, c = moe.moe_apply_grouped(held, cfg, h, first_expert=2 * s)
            part = jnp.stack([ref.experts(share, dict(p, experts=held[
                "experts"]), x, first=2 * s)[0] for x in h])
            np.testing.assert_allclose(np.asarray(y), np.asarray(part),
                                       rtol=1e-4, atol=1e-5)
            total = total + y
            counts = counts + int(jnp.sum(c["held_expert_tokens"]))
            load = c["expert_load"]
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert counts == 2 * 32 * 6          # every token's 6 pairs, once
    want_load = sum(ref.experts(whole, p, x)[2] for x in h)
    np.testing.assert_array_equal(np.asarray(load), np.asarray(want_load))


@pytest.mark.parametrize("pattern", [None, "MEEE*EE"])
def test_bias_update_rule(batch, pattern):
    """The loop moves each correction bias after every step by
    ``rate * sign(mean(load) - load)`` of that step's ``expert_load``
    counter (the reference's rule, from the program's counts), each row of
    the counter on its own expert layer, also where runs of expert layers
    are stacked into one segment; the step program itself leaves the bias
    alone (no gradient, no AdamW), and the counter is recorded beside
    ``held_expert_tokens``."""
    from repro.core.trace import TraceRecorder
    cfg = small_cfg()
    if pattern:
        cfg["hybrid_override_pattern"] = pattern
    model = adapter.program_model(cfg)
    params = adapter.program_params(_weights(cfg, 11), cfg)
    at = [i for i, (kind, _) in enumerate(structure(model.cfg))
          if kind == "moe"]
    bias0 = [jax.device_get(params["segments"][i]["moe"]["router_bias"])
             for i in at]
    opt = AdamW(lr=1e-3)

    class Source:
        def create(self, i):
            return batch

    step = jax.jit(make_train_step(model, opt))
    p1, _, met = step(params, opt.init(params), batch)
    for i, b in zip(at, bias0):
        np.testing.assert_array_equal(
            np.asarray(p1["segments"][i]["moe"]["router_bias"]), b)
    rec = TraceRecorder(enabled=True)
    res = train(model, Source(), steps=1, opt=opt, params=params,
                recorder=rec)
    load = np.asarray(met["expert_load"])
    assert load.shape == (sum(len(b) for b in bias0), 16)
    assert len(set(map(bytes, load))) == len(load)   # rows tell apart
    row = 0
    for i, b in zip(at, bias0):
        got = np.asarray(res["params"]["segments"][i]["moe"]["router_bias"])
        rows = load[row:row + len(b)]
        np.testing.assert_allclose(
            got, np.asarray(ref.bias_update(cfg, b, rows)), atol=1e-7)
        np.testing.assert_allclose(
            got, b + 1e-3 * np.sign(np.mean(rows, -1, keepdims=True)
                                    - rows), atol=1e-7)
        row += len(b)
    assert row == len(load)
    names = [e.name for e in rec.events() if e.kind == "counter"]
    assert names == ["held_expert_tokens", "expert_load"]


def test_capacity_path_refuses_what_it_cannot_compute():
    """The capacity path (no held share, not ragged) computes the softmax
    router and SiLU-gated experts only: a sigmoid router, a routed scale
    or relu^2 experts are refused, not silently trained as softmax."""
    cfg = dataclasses.replace(adapter.program_model(small_cfg()).cfg,
                              moe_ragged=False)
    m = dataclasses.replace(cfg.moe, n_held=0)
    soft = dataclasses.replace(m, router="softmax", routed_scale=1.0)
    x = jnp.zeros((1, 8, 64))

    def apply(c):
        p = moe.moe_init(jax.random.PRNGKey(0), c, jnp.float32)
        return moe.moe_apply(p, c, x)

    for c in (dataclasses.replace(cfg, moe=m),
              dataclasses.replace(cfg, act="swiglu", moe=dataclasses.replace(
                  soft, routed_scale=2.5)),
              dataclasses.replace(cfg, moe=soft)):
        with pytest.raises(ValueError, match="capacity path"):
            apply(c)
    y, _, _ = apply(dataclasses.replace(cfg, act="swiglu", moe=soft))
    assert y.shape == x.shape


def test_relu2_grouped_path_equals_a_loop_over_experts():
    """The grouped path's relu^2 experts (up and down in the grouped
    matmul, weighted by the renormalised, scaled sigmoid choice) equal a
    plain loop over the experts, held share and all."""
    cfg = adapter.program_model(small_cfg()).cfg
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                           n_shared=0))
    p = moe.moe_init(jax.random.PRNGKey(1), cfg, jnp.float32)
    p["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (16,))
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 64))
    with jax.default_matmul_precision("highest"):
        y, _, c = moe.moe_apply_grouped(p, cfg, h, first_expert=4)
        topv, topi, _ = moe.route(p, cfg.moe, h.reshape(-1, 64))
        want = jnp.zeros((32, 64))
        for e in range(4):
            w = jnp.sum(jnp.where(topi == 4 + e, topv, 0.0), -1)
            out = layers.relu2(h.reshape(-1, 64) @ p["experts"]["up"][e]) \
                @ p["experts"]["down"][e]
            want = want + w[:, None] * out
    np.testing.assert_allclose(np.asarray(y).reshape(32, 64),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    # the weights: chosen sigmoid scores, renormalised, times 2.5
    np.testing.assert_allclose(np.asarray(jnp.sum(topv, -1)), 2.5,
                               rtol=1e-6)
    assert [int(jnp.sum(topi == 4 + e)) for e in range(4)] == \
        c["held_expert_tokens"].tolist()


def test_registry_config_is_the_published_model():
    """The registry's full model: 52 layers in the published pattern, 23
    Mamba-2, 23 expert and 6 attention layers; Mamba heads given as
    published (64 x 64 = 4096, not expand * d_model = 5376)."""
    cfg = get_config("nemotron-3-nano-30b-a3b")
    kinds = [k for k, n in structure(cfg) for _ in range(n)]
    assert len(kinds) == 52 and kinds[:7] == [
        "mamba", "moe", "mamba", "moe", "mamba", "attn_only", "moe"]
    assert (kinds.count("mamba"), kinds.count("moe"),
            kinds.count("attn_only")) == (23, 23, 6)
    assert cfg.ssm.n_heads(cfg.d_model) == 64
    assert cfg.ssm.expand * cfg.d_model == 5376
    assert cfg.moe.n_held == 0 and cfg.moe.shared_width == 3712
    small = get_config("nemotron-3-nano-30b-a3b", reduced=True)
    assert small.pattern == "MEMEM*" and small.ssm.n_groups == 2
