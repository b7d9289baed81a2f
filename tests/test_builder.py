"""Sequential oracle ≡ compiled SPMD execution (paper P4), fan/merge
round-trips, logged execution."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (Collect, DataParallelCollect, Emit,
                        GroupOfPipelineCollects, Network, OnePipelineCollect,
                        TaskParallelOfGroupCollects, Worker, build,
                        run_sequential)
from repro.core.builder import _fan_merge, _fan_split


def _sq(x):
    return x * x


def _inc(x):
    return x + 1.0


def _add(a, x):
    return a + x


def _mk_items(n):
    return lambda i: jnp.asarray(float(i))


class TestOracleEquivalence:
    def test_farm(self):
        net = DataParallelCollect(create=_mk_items(8), function=_sq,
                                  collector=_add, init=jnp.asarray(0.0),
                                  workers=3, jit_combine=True)
        seq = run_sequential(net, 8)["collect"]
        par = build(net).run(instances=8)["collect"]
        assert float(seq) == pytest.approx(float(par))
        assert float(seq) == sum(i * i for i in range(8))

    def test_pipeline(self):
        net = OnePipelineCollect(create=_mk_items(6), stage_ops=[_sq, _inc],
                                 collector=_add, init=jnp.asarray(0.0),
                                 jit_combine=True)
        seq = run_sequential(net, 6)["collect"]
        par = build(net).run(instances=6)["collect"]
        assert float(seq) == pytest.approx(float(par))
        assert float(seq) == sum(i * i + 1 for i in range(6))

    @pytest.mark.parametrize("pattern", ["gop", "pog"])
    def test_composites(self, pattern):
        kw = dict(create=_mk_items(12), stage_ops=[_sq, _inc, _inc],
                  collector=_add, init=jnp.asarray(0.0), jit_combine=True)
        if pattern == "gop":
            net = GroupOfPipelineCollects(groups=3, **kw)
        else:
            net = TaskParallelOfGroupCollects(workers=3, **kw)
        seq = run_sequential(net, 12)["collect"]
        par = build(net).run(instances=12)["collect"]
        assert float(seq) == pytest.approx(float(par))
        assert float(seq) == sum(i * i + 2 for i in range(12))

    def test_gop_equals_pog_numerically(self):
        """The compiled realisations of the two equivalent topologies
        produce identical results (paper §9.2)."""
        kw = dict(create=_mk_items(8), stage_ops=[_sq, _inc],
                  collector=_add, init=jnp.asarray(0.0), jit_combine=True)
        a = build(GroupOfPipelineCollects(groups=2, **kw)).run(instances=8)
        b = build(TaskParallelOfGroupCollects(workers=2, **kw)).run(
            instances=8)
        assert float(a["collect"]) == pytest.approx(float(b["collect"]))

    def test_host_side_collector(self):
        """Non-jittable collector (dict building) folds host-side."""
        net = DataParallelCollect(
            create=_mk_items(5), function=_sq,
            collector=lambda acc, x: {**acc, len(acc): float(x)},
            init={}, workers=2, jit_combine=False)
        out = build(net).run(instances=5)["collect"]
        assert out == {i: float(i * i) for i in range(5)}


class TestFanMerge:
    @settings(max_examples=20, deadline=None)
    @given(n=st.integers(1, 5), k=st.integers(1, 4))
    def test_roundtrip(self, n, k):
        total = n * k
        x = jnp.arange(total * 3, dtype=jnp.float32).reshape(total, 3)
        parts = _fan_split(x, k)
        back = _fan_merge(parts) if k > 1 else parts[0]
        np.testing.assert_array_equal(np.asarray(back), np.asarray(x))

    def test_uneven_split_refused(self):
        from repro.core import NetworkError
        with pytest.raises(NetworkError, match="divisible"):
            _fan_split(jnp.arange(7.0), 2)


class TestLoggedExecution:
    def test_logs_and_bottleneck(self):
        net = DataParallelCollect(create=_mk_items(8), function=_sq,
                                  collector=_add, init=jnp.asarray(0.0),
                                  workers=2, jit_combine=True)
        cn = build(net)
        out = cn.run(instances=8, logged=True)
        assert float(out["collect"]) == sum(i * i for i in range(8))
        stages = {l.stage for l in cn.logs}
        assert "group" in stages and "collect" in stages
        rep = cn.log_report()
        assert "bottleneck" in rep

    def test_netlog_visualisation(self):
        """Paper §13 future work: timeline + topology deduced from the DSL."""
        from repro.core import netlog
        net = DataParallelCollect(create=_mk_items(8), function=_sq,
                                  collector=_add, init=jnp.asarray(0.0),
                                  workers=2, jit_combine=True)
        cn = build(net)
        cn.run(instances=8, logged=True)
        rep = netlog.report(cn)
        assert "bottleneck" in rep and "network" in rep
        assert "spreader/fan" in rep and "reducer/merge" in rep
        assert "█" in rep

    def test_logged_equals_fused(self):
        net = OnePipelineCollect(create=_mk_items(6), stage_ops=[_sq, _inc],
                                 collector=_add, init=jnp.asarray(0.0),
                                 jit_combine=True)
        cn = build(net)
        assert float(cn.run(instances=6)["collect"]) == pytest.approx(
            float(cn.run(instances=6, logged=True)["collect"]))


class TestEmitWithLocal:
    def test_local_state_threads(self):
        from repro.core import EmitWithLocal, AnyFanOne, OneFanAny

        def create(i, local):  # running sum as local state (sieve-like)
            local = local + i
            return jnp.asarray(float(local)), local

        net = Network("loc")
        net.add(EmitWithLocal(create, lambda: 0, name="emit"),
                OneFanAny(name="s"),
                Worker(lambda x: x, name="w"),
                AnyFanOne(name="r"),
                Collect(_add, init=jnp.asarray(0.0), jit_combine=True,
                        name="collect"))
        seq = run_sequential(net, 5)["collect"]
        par = build(net).run(instances=5)["collect"]
        # emitted: 0,1,3,6,10 → sum 20
        assert float(seq) == 20.0 == float(par)
