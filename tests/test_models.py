"""Per-architecture smoke tests (assignment requirement): reduced config of
the same family, one forward/train step on CPU, output shapes + no NaNs;
plus decode-vs-forward consistency and family-specific checks."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models import Model

ALL_ARCHS = sorted(ARCHS)


@pytest.fixture(scope="module")
def toks(key):
    return jax.random.randint(key, (2, 24), 0, 200)


@pytest.mark.parametrize("arch", ALL_ARCHS)
class TestArchSmoke:
    def test_forward_and_train_step(self, arch, key, toks):
        cfg = get_config(arch, reduced=True)
        m = Model(cfg)
        params = m.init(key)
        logits, aux = jax.jit(m.forward)(params, toks)
        assert logits.shape == (2, 24, cfg.vocab)
        assert np.isfinite(np.asarray(logits, np.float32)).all()
        batch = {"tokens": toks, "labels": toks}
        loss, metrics = jax.jit(m.loss_fn)(params, batch)
        assert np.isfinite(float(loss))
        g = jax.grad(lambda p: m.loss_fn(p, batch)[0])(params)
        leaves = jax.tree_util.tree_leaves(g)
        assert all(np.isfinite(np.asarray(x, np.float32)).all()
                   for x in leaves)
        assert any(float(jnp.max(jnp.abs(x))) > 0 for x in leaves), \
            "gradients all zero"

    def test_decode_matches_forward(self, arch, key, toks):
        cfg = get_config(arch, reduced=True)
        m = Model(cfg)
        params = m.init(key)
        kw = {}
        if cfg.family == "audio":
            # enc-dec: pin the SAME stub frames for forward and prefill
            import jax.numpy as jnp2
            frames = jnp2.zeros((2, 6, cfg.d_model), jnp2.float32)
            full_logits, _ = jax.jit(
                lambda p, t: m.forward(p, t, frames=frames))(params, toks)
            kw["frames"] = frames
        else:
            full_logits, _ = jax.jit(m.forward)(params, toks)
        logits_p, cache = m.prefill(params, toks[:, :12], max_len=32, **kw)
        err = float(jnp.max(jnp.abs(
            logits_p[:, -1].astype(jnp.float32)
            - full_logits[:, 11].astype(jnp.float32))))
        assert err < 3e-3, f"prefill diverges from forward: {err}"
        logits_d, cache = jax.jit(m.decode_step)(params, cache,
                                                 toks[:, 12:13])
        err = float(jnp.max(jnp.abs(
            logits_d[:, -1].astype(jnp.float32)
            - full_logits[:, 12].astype(jnp.float32))))
        assert err < 3e-3, f"decode diverges from forward: {err}"


class TestFamilySpecific:
    def test_moe_aux_loss_positive(self, key, toks):
        cfg = get_config("phi3.5-moe-42b-a6.6b", reduced=True)
        m = Model(cfg)
        params = m.init(key)
        _, aux = jax.jit(m.forward)(params, toks)
        assert float(aux) > 0.0  # load-balancing loss active

    def test_deepseek_layer0_dense(self):
        from repro.models.transformer import structure
        cfg = get_config("deepseek-moe-16b")
        assert structure(cfg)[0] == ("attn", 1)
        assert structure(cfg)[1] == ("attn_moe", 27)

    def test_deepseek_v2_lite_structure_and_reduce(self):
        """Layer 0 dense, then 26 MLA + held-expert layers; reduce() keeps
        latent attention, YaRN, a held share and the balance loss."""
        from repro.models.transformer import structure
        cfg = get_config("deepseek-v2-lite")
        assert structure(cfg) == [("attn", 1), ("attn_moe", 26)]
        assert (cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.n_held) == (64, 6, 8)
        small = get_config("deepseek-v2-lite", reduced=True)
        assert small.mla and small.yarn and small.moe.seq_aux
        assert 0 < small.moe.n_held < small.moe.n_experts

    def test_zamba_shared_block_is_shared(self, key):
        """The shared attention block's params appear once (weight tying)."""
        cfg = get_config("zamba2-1.2b", reduced=True)
        m = Model(cfg)
        params = m.init(key)
        assert "shared_block" in params
        from repro.models.transformer import n_shared_applications
        assert n_shared_applications(cfg) >= 1

    def test_zamba_full_structure(self):
        from repro.models.transformer import structure
        cfg = get_config("zamba2-1.2b")
        segs = structure(cfg)
        assert sum(c for k, c in segs if k == "mamba") == 38
        assert sum(1 for k, _ in segs if k == "shared_attn") == 6

    def test_mamba_attention_free(self, key):
        from repro.models.transformer import structure
        cfg = get_config("mamba2-2.7b")
        assert all(k == "mamba" for k, _ in structure(cfg))

    def test_gemma_embed_scaling(self, key):
        cfg = get_config("gemma-2b", reduced=True)
        cfg2 = dataclasses.replace(cfg, embed_scale=False)
        m1, m2 = Model(cfg), Model(cfg2)
        p = m1.init(key)
        t = jnp.zeros((1, 4), jnp.int32)
        l1, _ = m1.forward(p, t)
        l2, _ = m2.forward(p, t)
        assert float(jnp.max(jnp.abs(l1 - l2))) > 0  # scaling has effect

    def test_mrope_positions_shape(self, key):
        cfg = get_config("qwen2-vl-2b", reduced=True)
        from repro.models.transformer import _positions
        pos = _positions(cfg, jnp.zeros((2, 8), jnp.int32))
        assert pos.shape == (2, 8, 3)

    def test_glm4_partial_rotary(self):
        cfg = get_config("glm4-9b")
        assert cfg.rope_fraction == 0.5
        rot = int(cfg.hd * cfg.rope_fraction) // 2 * 2
        assert rot == cfg.hd // 2


class TestRaggedMoE:
    """Ragged grouped-matmul MoE ≡ dropless capacity MoE (forward + grads;
    both paths take the same per-sequence balance loss)."""

    @pytest.mark.parametrize("arch", ["deepseek-moe-16b",
                                      "phi3.5-moe-42b-a6.6b"])
    def test_equals_dropless_capacity(self, arch, key, toks):
        cfg = get_config(arch, reduced=True)  # reduced = dropless capacity
        cfg_r = dataclasses.replace(cfg, moe_ragged=True)
        m1, m2 = Model(cfg), Model(cfg_r)
        params = m1.init(key)
        l1, _ = jax.jit(m1.forward)(params, toks)
        l2, _ = jax.jit(m2.forward)(params, toks)
        assert float(jnp.max(jnp.abs(l1 - l2))) < 1e-4
        batch = {"tokens": toks, "labels": toks}
        g1 = jax.grad(lambda p: m1.loss_fn(p, batch, aux_weight=0.0)[0])(
            params)
        g2 = jax.grad(lambda p: m2.loss_fn(p, batch, aux_weight=0.0)[0])(
            params)
        d = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)))
        assert d < 1e-4, f"ragged grads diverge: {d}"


class TestChunkedAttention:
    """Query-chunked attention (the XLA-level flash analogue) is exact."""

    def test_forward_identical(self, key, toks):
        cfg = get_config("gemma-2b", reduced=True)
        cfg_c = dataclasses.replace(cfg, attn_chunk=8)
        m1, m2 = Model(cfg), Model(cfg_c)
        params = m1.init(key)
        l1, _ = jax.jit(m1.forward)(params, toks)
        l2, _ = jax.jit(m2.forward)(params, toks)
        assert float(jnp.max(jnp.abs(l1 - l2))) < 1e-5

    def test_grads_identical(self, key, toks):
        cfg = get_config("qwen2-0.5b", reduced=True)
        cfg_c = dataclasses.replace(cfg, attn_chunk=8)
        m1, m2 = Model(cfg), Model(cfg_c)
        params = m1.init(key)
        batch = {"tokens": toks, "labels": toks}
        g1 = jax.grad(lambda p: m1.loss_fn(p, batch)[0])(params)
        g2 = jax.grad(lambda p: m2.loss_fn(p, batch)[0])(params)
        d = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)))
        assert d < 1e-5


class TestKVQuant:
    """int8 KV cache (serving §Perf lever): greedy decode unchanged."""

    def test_greedy_decode_identical(self, key):
        cfg = get_config("qwen2-0.5b", reduced=True)
        cfg_q = dataclasses.replace(cfg, kv_quant=True)
        m, mq = Model(cfg), Model(cfg_q)
        params = m.init(key)
        toks = jax.random.randint(key, (2, 8), 0, cfg.vocab)

        def gen(model, n=8):
            logits, cache = model.prefill(params, toks, max_len=32)
            dj = jax.jit(model.decode_step)
            t = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
            out = []
            for _ in range(n):
                out.append(np.asarray(t))
                logits, cache = dj(params, cache, t[:, None])
                t = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
            return np.stack(out)

        assert (gen(m) == gen(mq)).all()

    def test_cache_half_size(self):
        cfg = get_config("yi-34b")
        import dataclasses as dc
        m = Model(dc.replace(cfg, param_dtype="bfloat16"))
        mq = Model(dc.replace(cfg, param_dtype="bfloat16", kv_quant=True))
        c = jax.eval_shape(lambda: m.init_cache(2, 1024))
        cq = jax.eval_shape(lambda: mq.init_cache(2, 1024))
        size = lambda t: sum(  # noqa: E731
            x.size * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(t))
        assert size(cq) < 0.55 * size(c)


class TestAdvanceMask:
    """Continuous-batching contract: advance=False freezes a row."""

    @pytest.mark.parametrize("arch", ["qwen2-0.5b", "mamba2-2.7b",
                                      "zamba2-1.2b"])
    def test_frozen_row_unchanged(self, arch, key):
        cfg = get_config(arch, reduced=True)
        m = Model(cfg)
        params = m.init(key)
        cache = m.init_cache(2, 16)
        t = jnp.asarray([[3], [5]], jnp.int32)
        adv = jnp.asarray([True, False])
        _, c1 = m.decode_step(params, cache, t, advance=adv)
        assert int(c1["step"][0]) == 1 and int(c1["step"][1]) == 0
        # row 1 state identical to init
        def row(tree, i):
            return [np.asarray(l)[..., i, :] if False else None
                    for l in jax.tree_util.tree_leaves(tree)]
        # decoding row 1 from c1 (where only row 0 advanced) must equal
        # decoding it from the untouched initial cache
        l_after, _ = m.decode_step(params, c1, t,
                                   advance=jnp.asarray([False, True]))
        l_ref, _ = m.decode_step(params, cache, t,
                                 advance=jnp.asarray([False, True]))
        np.testing.assert_allclose(
            np.asarray(l_after[1], np.float32),
            np.asarray(l_ref[1], np.float32), rtol=2e-4, atol=2e-4)
