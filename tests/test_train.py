"""Training substrate: optimizer, grad accumulation, checkpoint/restart,
fault tolerance, data pipeline."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import Prefetcher, SyntheticLM
from repro.models import Model
from repro.train import (AdamW, Checkpointer, FaultInjector,
                         FaultTolerantRunner, cosine_warmup, make_train_step,
                         train)
from repro.train.optimizer import clip_by_global_norm, global_norm

pytestmark = pytest.mark.slow  # excluded from the fast CI lane


class TestOptimizer:
    def test_quadratic_convergence(self):
        opt = AdamW(lr=0.1, weight_decay=0.0)
        params = {"w": jnp.asarray([5.0, -3.0])}
        state = opt.init(params)
        for _ in range(200):
            g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
            params, state, _ = opt.update(g, state, params)
        assert float(jnp.max(jnp.abs(params["w"]))) < 1e-2

    def test_clip_by_global_norm(self):
        tree = {"a": jnp.ones(4) * 10.0}
        clipped, norm = clip_by_global_norm(tree, 1.0)
        assert float(norm) == pytest.approx(20.0)
        assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-4)

    def test_cosine_warmup_shape(self):
        lr = cosine_warmup(1.0, warmup=10, total=100)
        assert float(lr(0)) == 0.0
        assert float(lr(10)) == pytest.approx(1.0)
        assert float(lr(100)) == pytest.approx(0.1, rel=1e-2)
        assert float(lr(55)) < float(lr(20))


class TestTrainStep:
    def test_grad_accum_equivalence(self, key):
        """accum=2 over the same global batch ≈ accum=1 (same update)."""
        cfg = get_config("qwen2-0.5b", reduced=True)
        model = Model(cfg)
        params = model.init(key)
        opt = AdamW(lr=1e-3)
        src = SyntheticLM(batch=8, seq=16, vocab=cfg.vocab)
        batch = src.create(0)
        s1 = make_train_step(model, opt, grad_accum=1)
        s2 = make_train_step(model, opt, grad_accum=2)
        p1, _, m1 = jax.jit(s1)(params, opt.init(params), batch)
        p2, _, m2 = jax.jit(s2)(params, opt.init(params), batch)
        d = max(float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree_util.tree_leaves(p1),
                                jax.tree_util.tree_leaves(p2)))
        assert d < 5e-5, f"accum changes update: {d}"

    def test_loss_chunk_equivalence(self, key):
        """Chunked CE (the §Perf memory lever) is numerically identical."""
        import dataclasses
        cfg = get_config("qwen2-0.5b", reduced=True)
        cfg2 = dataclasses.replace(cfg, loss_chunk=8)
        m1, m2 = Model(cfg), Model(cfg2)
        params = m1.init(key)
        src = SyntheticLM(batch=4, seq=32, vocab=cfg.vocab)
        batch = src.create(0)
        l1, _ = jax.jit(m1.loss_fn)(params, batch)
        l2, _ = jax.jit(m2.loss_fn)(params, batch)
        assert abs(float(l1) - float(l2)) < 1e-5
        g1 = jax.grad(lambda p: m1.loss_fn(p, batch)[0])(params)
        g2 = jax.grad(lambda p: m2.loss_fn(p, batch)[0])(params)
        d = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)))
        assert d < 1e-5, f"chunked grads diverge: {d}"

    def test_loss_decreases(self, key):
        cfg = get_config("qwen2-0.5b", reduced=True)
        model = Model(cfg)
        src = SyntheticLM(batch=8, seq=32, vocab=cfg.vocab)
        res = train(model, src, steps=40, opt=AdamW(lr=1e-2), key=key,
                    log_every=1)
        losses = [h["loss"] for h in res["history"]]
        first = sum(losses[:5]) / 5
        last = sum(losses[-5:]) / 5  # step noise: compare window means
        assert last < first - 0.25, (first, last)


class TestTrainTrace:
    """``train()`` records a ``data`` span and a ``train_step`` span per
    step, and, where the expert layers hold a share, the tokens routed to
    each held expert as a ``held_expert_tokens`` counter."""

    def test_spans_and_held_expert_counter(self, key):
        from repro.core.trace import TraceRecorder
        cfg = get_config("deepseek-v2-lite", reduced=True)
        model = Model(cfg)
        src = SyntheticLM(batch=2, seq=16, vocab=cfg.vocab)
        rec = TraceRecorder()
        res = train(model, src, steps=3, key=key, recorder=rec)
        ev = rec.events()
        spans = [(e.name, e.args["step"]) for e in ev if e.kind == "span"]
        assert spans == [(n, i) for i in range(3)
                         for n in ("data", "train_step")]
        counts = [e for e in ev if e.kind == "counter"]
        assert [e.name for e in counts] == ["held_expert_tokens"] * 3
        assert [e.args["step"] for e in counts] == [0, 1, 2]
        held = np.asarray(counts[0].args["value"])
        n_moe = cfg.n_layers - 1
        assert held.shape == (n_moe, cfg.moe.n_held)
        # each token routes k pairs in each expert layer; some land here
        assert 0 < held.sum() <= n_moe * 2 * 16 * cfg.moe.top_k
        step = [e for e in ev if e.name == "train_step"]
        assert all(c.ts >= s.ts + s.dur for c, s in zip(counts, step))
        assert res["step"] == 3 and "held_expert_tokens" not in \
            res["history"][0]

    def test_disabled_recorder_records_nothing(self, key):
        """Tracing off, ``train`` records nothing (and so never waits on a
        step's loss); the jitted step it returns runs again."""
        from repro.core.trace import TraceRecorder
        cfg = get_config("deepseek-v2-lite", reduced=True)
        model = Model(cfg)
        src = SyntheticLM(batch=2, seq=16, vocab=cfg.vocab)
        rec = TraceRecorder(enabled=False)
        res = train(model, src, steps=2, key=key, recorder=rec)
        assert rec.events() == [] and res["step"] == 2
        params, opt_state, metrics = res["step_fn"](
            res["params"], res["opt_state"], src.create(2))
        assert np.isfinite(float(metrics["loss"]))
        assert int(opt_state["step"]) == 3

    def test_on_step_ends_the_loop(self, key):
        cfg = get_config("qwen2-0.5b", reduced=True)
        model = Model(cfg)
        src = SyntheticLM(batch=2, seq=16, vocab=cfg.vocab)
        seen = []
        res = train(model, src, steps=10, key=key,
                    on_step=lambda i, *_: seen.append(i) or i == 2)
        assert seen == [0, 1, 2] and res["step"] == 3
        assert res["history"][-1]["step"] == 2


class TestCheckpoint:
    def test_roundtrip_exact(self, key):
        tree = {"a": jnp.arange(12.0).reshape(3, 4),
                "b": {"c": jnp.asarray([1, 2, 3], jnp.int32)}}
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d)
            ck.save(7, tree)
            step, restored = ck.restore(tree)
            assert step == 7
            for x, y in zip(jax.tree_util.tree_leaves(tree),
                            jax.tree_util.tree_leaves(restored)):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_latest_pointer_and_gc(self):
        tree = {"x": jnp.zeros(3)}
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d, keep=2)
            for s in (1, 2, 3, 4):
                ck.save(s, tree)
            assert ck.latest_step() == 4
            steps = sorted(x for x in os.listdir(d) if x.startswith("step_"))
            assert len(steps) == 2  # GC kept the last two

    def test_async_save(self):
        tree = {"x": jnp.ones(100)}
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d, async_save=True)
            ck.save(1, tree)
            ck.wait()
            assert ck.latest_step() == 1

    def test_structure_mismatch_refused(self):
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d)
            ck.save(1, {"a": jnp.zeros(3)})
            with pytest.raises(AssertionError, match="structure mismatch"):
                ck.restore({"a": jnp.zeros(3), "b": jnp.zeros(2)})

    def test_corrupt_latest_falls_back_to_previous_step(self):
        """A torn write (process killed mid-save) must not take restore()
        down with it: the truncated latest step is skipped and the
        previous complete step restores."""
        like = {"x": jnp.zeros(4)}
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d, keep=3)
            ck.save(1, {"x": jnp.arange(4.0)})
            ck.save(2, {"x": jnp.arange(4.0) * 10})
            # simulate the mid-write kill: the renamed step_00000002 exists
            # but one leaf blob is truncated to garbage
            leaf = os.path.join(d, "step_00000002", "leaf_00000.npy")
            with open(leaf, "wb") as f:
                f.write(b"\x93NUMPY")  # header cut short
            step, restored = ck.restore(like)
            assert step == 1
            np.testing.assert_array_equal(np.asarray(restored["x"]),
                                          np.arange(4.0))
            # an explicit step= stays strict: the caller asked for exactly
            # that snapshot, so the corruption must surface
            with pytest.raises(Exception):
                ck.restore(like, step=2)

    def test_tmp_dir_from_killed_write_is_invisible(self):
        """A kill BEFORE the atomic rename leaves only step_X.tmp — which
        neither restore() nor steps_on_disk() may see."""
        like = {"x": jnp.zeros(2)}
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d)
            ck.save(5, {"x": jnp.asarray([1.0, 2.0])})
            os.makedirs(os.path.join(d, "step_00000006.tmp"))
            assert ck.steps_on_disk() == [5]
            step, restored = ck.restore(like)
            assert step == 5
            np.testing.assert_array_equal(np.asarray(restored["x"]),
                                          [1.0, 2.0])

    def test_all_steps_corrupt_reraises(self):
        like = {"x": jnp.zeros(2)}
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d)
            ck.save(1, {"x": jnp.ones(2)})
            leaf = os.path.join(d, "step_00000001", "leaf_00000.npy")
            with open(leaf, "wb") as f:
                f.write(b"junk")
            with pytest.raises(Exception):
                ck.restore(like)


class TestFaultTolerance:
    def test_injected_failures_recovered(self, key):
        cfg = get_config("qwen2-0.5b", reduced=True)
        model = Model(cfg)
        opt = AdamW(lr=1e-3)
        src = SyntheticLM(batch=4, seq=16, vocab=cfg.vocab)
        params = model.init(key)
        state = {"params": params, "opt_state": opt.init(params)}
        sfj = jax.jit(make_train_step(model, opt))

        def step_fn(i, st):
            b = src.create(i)
            p, o, _ = sfj(st["params"], st["opt_state"], b)
            return {"params": p, "opt_state": o}

        with tempfile.TemporaryDirectory() as d:
            runner = FaultTolerantRunner(Checkpointer(d), max_restarts=3)
            inj = FaultInjector(fail_at=(4, 9))
            final = runner.run(total_steps=12, state=state, step_fn=step_fn,
                               save_every=3, injector=inj)
            assert runner.restarts == 2
            # deterministic data ⇒ final state equals a clean 12-step run
            clean = {"params": params, "opt_state": opt.init(params)}
            for i in range(12):
                clean = step_fn(i, clean)
            d_max = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
                jax.tree_util.tree_leaves(final["params"]),
                jax.tree_util.tree_leaves(clean["params"])))
            assert d_max < 1e-6, "restart-recovered run diverges"

    def test_exceeding_restarts_raises(self, key):
        with tempfile.TemporaryDirectory() as d:
            runner = FaultTolerantRunner(Checkpointer(d), max_restarts=1)

            def bad_step(i, st):
                raise RuntimeError("permafail")

            with pytest.raises(RuntimeError, match="max_restarts"):
                runner.run(total_steps=3, state={"x": jnp.zeros(1)},
                           step_fn=bad_step, save_every=1)


class TestDataPipeline:
    def test_synthetic_deterministic(self):
        src = SyntheticLM(batch=2, seq=8, vocab=100, seed=3)
        a = src.create(5)
        b = src.create(5)
        np.testing.assert_array_equal(np.asarray(a["tokens"]),
                                      np.asarray(b["tokens"]))
        # labels are next-token shifted
        full = SyntheticLM(batch=2, seq=8, vocab=100, seed=3)
        c = full.create(5)
        np.testing.assert_array_equal(np.asarray(c["labels"][:, :-1]),
                                      np.asarray(c["tokens"][:, 1:]))

    def test_prefetcher_order_and_ut(self):
        src = SyntheticLM(batch=1, seq=4, vocab=50)
        pf = Prefetcher(src, depth=2, n_steps=5)
        steps = [s for s, _ in pf]
        assert steps == [0, 1, 2, 3, 4]  # ordered, then UT terminates
