"""The unified tracing + metrics plane (``repro.core.trace``): recorder
semantics, deterministic Chrome export, cross-host merge on live cluster
deployments, the autoscaler's MetricsSnapshot feed, and online CSP
conformance — the recorded run projected onto the model's trace set."""

import json

import jax.numpy as jnp
import pytest

from repro.cluster.deploy import ClusterDeployment
from repro.cluster.sim import SimTransport
from repro.core import (DataParallelCollect, OnePipelineCollect, build,
                        trace)
from repro.core.dataflow import NetworkError
from repro.core.trace import (CountingClock, TraceRecorder, export_chrome,
                              merge_events)


def _farm(workers=2, explicit=False):
    return DataParallelCollect(
        create=lambda i: jnp.asarray(float(i)),
        function=lambda x: x * x,
        collector=lambda a, x: a + x, init=jnp.asarray(0.0),
        workers=workers, jit_combine=True, explicit=explicit)


def _pipeline():
    return OnePipelineCollect(
        create=lambda i: jnp.asarray(float(i)),
        stage_ops=[lambda x: x * x, lambda x: x + 1.0],
        collector=lambda a, x: a + x, init=jnp.asarray(0.0),
        jit_combine=True)


# module-level factory: pipe-transport hosts rebuild the net from this
def _farm_factory(workers):
    return DataParallelCollect(
        create=lambda i: jnp.asarray(float(i)),
        function=lambda x: x * x,
        collector=lambda a, x: a + x, init=jnp.asarray(0.0),
        workers=workers, jit_combine=True)


class TestRecorder:
    def test_span_instant_counter(self):
        rec = TraceRecorder(host="h", clock=CountingClock())
        with rec.span("work", "cat", ci=3) as sp:
            sp.set(nbytes=16)
        rec.instant("mark", "cat", ci=3)
        rec.counter("depth", 7, "cat")
        ev = rec.events()
        assert [e.kind for e in ev] == ["span", "instant", "counter"]
        span, inst, ctr = ev
        assert span.host == "h" and span.name == "work"
        assert span.ts == 1.0 and span.dur == 1.0  # counting clock ticks
        assert span.args == {"ci": 3, "nbytes": 16}
        assert inst.ts == 3.0 and inst.args == {"ci": 3}
        assert ctr.args["value"] == 7

    def test_disabled_records_nothing(self):
        rec = TraceRecorder(enabled=False)
        with rec.span("work") as sp:
            sp.set(x=1)
        rec.instant("mark")
        rec.counter("depth", 1)
        assert len(rec) == 0
        # the disabled span is the shared null object — no allocation
        assert rec.span("a") is rec.span("b")

    def test_capacity_bounds_the_ring(self):
        rec = TraceRecorder(capacity=4, clock=CountingClock())
        for i in range(10):
            rec.instant("e", i=i)
        ev = rec.events()
        assert len(ev) == 4
        assert [e.args["i"] for e in ev] == [6, 7, 8, 9]  # oldest dropped

    def test_drain_ships_and_clears(self):
        rec = TraceRecorder(clock=CountingClock())
        rec.instant("a")
        raw, now, virtual = rec.drain()
        assert len(raw) == 1 and virtual and now == 2.0
        assert len(rec) == 0

    def test_process_default_enable_disable(self):
        assert not trace.current().enabled
        rec = trace.enable(host="t")
        try:
            assert trace.current() is rec and rec.enabled
            rec.instant("x")
            assert len(rec) == 1
        finally:
            trace.disable()
        assert not trace.current().enabled and len(trace.current()) == 0


class TestMergeAndExport:
    def test_merge_applies_offsets_stably(self):
        a = TraceRecorder(host="a", clock=CountingClock())
        b = TraceRecorder(host="b", clock=CountingClock())
        for i in range(3):
            a.instant("ea", i=i)
            b.instant("eb", i=i)
        merged = merge_events([("a", 10.0, a.drain()[0]),
                               ("b", 0.0, b.drain()[0])])
        # b's events (ts 1..3) land before a's offset events (ts 11..13),
        # and each host's own order survives
        assert [e.host for e in merged] == ["b", "b", "b", "a", "a", "a"]
        assert [e.args["i"] for e in merged] == [0, 1, 2, 0, 1, 2]

    def test_export_golden_literal(self):
        rec = TraceRecorder(host=0, clock=CountingClock())
        with rec.span("step", "run", ci=0):
            pass
        rec.instant("mark", "run")
        blob = export_chrome(rec.events())
        assert blob == (
            '{"displayTimeUnit":"ms","traceEvents":['
            '{"args":{"name":"host 0"},"name":"process_name","ph":"M",'
            '"pid":0,"tid":0},'
            '{"args":{"ci":0},"cat":"run","dur":1000000.0,"name":"step",'
            '"ph":"X","pid":0,"tid":0,"ts":1000000.0},'
            '{"args":{},"cat":"run","name":"mark","ph":"i","pid":0,'
            '"s":"t","tid":0,"ts":3000000.0}]}')

    def test_export_byte_identical_across_runs(self, tmp_path):
        def one():
            rec = TraceRecorder(host="w", clock=CountingClock())
            for i in range(4):
                with rec.span("s", ci=i):
                    rec.counter("c", i)
            return export_chrome(rec.events())

        assert one() == one()
        p = tmp_path / "t.json"
        export_chrome([], str(p))
        assert json.loads(p.read_text()) == {"traceEvents": [],
                                             "displayTimeUnit": "ms"}


class TestStreamInstrumentation:
    def test_streaming_records_and_conforms(self):
        net = _farm()
        cn = build(net)
        rec = trace.enable(host=0)
        try:
            out = cn.run_streaming(instances=8, microbatch_size=2)
            ev = rec.events()
        finally:
            trace.disable()
        assert float(out["collect"]) == sum(i * i for i in range(8))
        names = {e.name for e in ev}
        assert {"stage", "collect", "dispatch", "in_flight"} <= names
        conf = trace.check_conformance(net, ev)
        assert conf.ok and conf.coverage == 1.0, conf.detail

    def test_disabled_is_invisible(self):
        cn = build(_farm())
        a = cn.run_streaming(instances=6, microbatch_size=2)
        assert len(trace.current()) == 0
        rec = trace.enable(host=0)
        try:
            b = cn.run_streaming(instances=6, microbatch_size=2)
            assert len(rec) > 0
        finally:
            trace.disable()
        assert float(a["collect"]) == float(b["collect"])

    @pytest.mark.parametrize("make", [lambda: _farm(explicit=True),
                                      _pipeline])
    def test_conformance_across_topologies(self, make):
        net = make()
        cn = build(net)
        rec = trace.enable(host=0)
        try:
            cn.run_streaming(instances=6, microbatch_size=2)
            conf = trace.check_conformance(net, rec.events())
        finally:
            trace.disable()
        assert conf.ok, conf.detail

    def test_conformance_flags_missing_chunks(self):
        net = _farm()
        cn = build(net)
        rec = trace.enable(host=0)
        try:
            cn.run_streaming(instances=6, microbatch_size=2)
            ev = [e for e in rec.events()
                  if not (e.name == "collect" and e.args.get("ci") == 0)]
        finally:
            trace.disable()
        conf = trace.check_conformance(net, ev)
        assert not conf.ok and conf.coverage < 1.0
        assert "never folded" in conf.detail

    def test_conformance_rejects_combine(self):
        from repro.core import Collect, CombineNto1, Emit, Network
        from repro.core.processes import OneSeqCastList, Worker

        net = Network("combine")
        net.add(Emit(lambda i: jnp.asarray(float(i)), name="emit"))
        net.add(OneSeqCastList(name="cast"))
        for w in range(2):
            net.procs[f"w{w}"] = Worker(lambda x: x + 1.0, name=f"w{w}",
                                        tag=f"f{w}")
            net.connect("cast", f"w{w}")
        net.procs["comb"] = CombineNto1(lambda a, b: a + b, name="comb")
        net.connect("w0", "comb")
        net.connect("w1", "comb")
        net._tail = "comb"
        net.add(Collect(lambda a, x: a + x, init=jnp.asarray(0.0),
                        jit_combine=True, name="collect"))
        conf = trace.check_conformance(net, [])
        assert not conf.ok and "COMBINE" in conf.detail


class TestClusterTrace:
    def test_inprocess_merge_metrics_conformance(self):
        net = _farm_factory(2)
        with ClusterDeployment(net, hosts=2, transport="inprocess",
                               microbatch_size=2, trace=True) as dep:
            out = dep.run(instances=8)
            ev = dep.merged_trace()
            hosts = {e.host for e in ev}
            assert hosts == {0, 1, "ctrl"}
            conf = trace.check_conformance(net, ev)
            assert conf.ok and conf.coverage == 1.0, conf.detail
            # transport send/recv spans carry byte counts
            sends = [e for e in ev if e.name == "send"]
            assert sends and all(e.args["nbytes"] > 0 for e in sends)
            m = dep.metrics()
            assert m.epoch == out.epoch == 1
            assert set(m.queue_depths) == {"group->afo"}
            assert set(m.throughput) == {0, 1}
            assert all(v >= 0 for v in m.stall_rate.values())
            assert m.describe().startswith("metrics @ epoch 1")
            # chrome export parses, one pid per host + ctrl
            doc = json.loads(dep.export_trace())
            assert len({e["pid"] for e in doc["traceEvents"]}) == 3
            dep.clear_trace()
            assert dep.merged_trace() == []

    def test_pipe_merge_covers_spawned_hosts(self):
        net = _farm_factory(2)
        with ClusterDeployment(net, hosts=2, transport="pipe",
                               microbatch_size=2, trace=True,
                               factory=(_farm_factory, (2,))) as dep:
            dep.run(instances=6)
            ev = dep.merged_trace()
            assert {e.host for e in ev} == {0, 1, "ctrl"}
            conf = trace.check_conformance(net, ev)
            assert conf.ok, conf.detail
            # merged per-host order is monotone after offset alignment
            last = {}
            for e in ev:
                assert e.ts >= last.get(e.host, float("-inf"))
                last[e.host] = e.ts

    def test_untraced_deployment_records_nothing(self):
        net = _farm_factory(2)
        with ClusterDeployment(net, hosts=2, transport="inprocess",
                               microbatch_size=2) as dep:
            dep.run(instances=6)
            assert dep.merged_trace() == []
            m = dep.metrics()  # metrics don't need tracing
            assert set(m.throughput) == {0, 1}

    def test_bytes_per_s_survives_reconfigure(self):
        """Regression: per-channel bytes/s came from the LAST batch's
        reports, so a reconfigure() (which replaces the report map and
        bumps the epoch) zeroed every channel's rate until the next batch
        — and dropped channels the new plan no longer cuts entirely.  The
        snapshot now reports deployment-lifetime cumulative rates."""
        net = _farm_factory(2)
        with ClusterDeployment(net, hosts=2, transport="inprocess",
                               microbatch_size=2) as dep:
            dep.run(instances=8)
            before = dep.metrics().bytes_per_s
            assert before and all(v > 0 for v in before.values())
            dep.reconfigure(hosts=1)  # replaces _last_reports, bumps epoch
            after = dep.metrics()
            assert after.epoch == 2
            for chan_key, rate in before.items():
                assert after.bytes_per_s.get(chan_key, 0) > 0, (
                    f"{chan_key} rate reset across reconfigure")
            dep.reconfigure(hosts=2)
            dep.run(instances=8)
            final = dep.metrics().bytes_per_s
            # the ledger accumulates: the cut channel's rate is still live
            for chan_key in before:
                assert final.get(chan_key, 0) > 0

    def test_ledger_absorbs_only_accepted_successes(self):
        """Regression: channel totals were folded into the cumulative
        ledger BEFORE the batch-id staleness check, so an abandoned batch's
        late success (and a stalled host's partial report, which is re-run
        and re-reported) double-counted its bytes."""
        from repro.cluster.control import ClusterController
        from repro.cluster.runtime import HostReport

        c = object.__new__(ClusterController)
        c.timeout_s, c.poll_s, c.epoch = 5.0, 0.01, 1
        c._procs, c._cum_chan = {}, {}
        c._stalled, c._dead, c._erred = {}, set(), set()
        c._absorb_trace = lambda *a: None
        c._quiesce = lambda *a: None
        stale = ("ok", 0, 98, 1, None,
                 ("", "", 0, {"wall_s": 1.0,
                              "sent_bytes": {"a->b": 7777}}, None))
        stalled = ("stalled", 1, 99, 1, (3, "tb"),
                   ("", "", 0, {"wall_s": 1.0,
                                "sent_bytes": {"a->b": 5555}}, None))
        good = ("ok", 0, 99, 1, None,
                ("", "", 0, {"wall_s": 2.0,
                             "sent_bytes": {"a->b": 1000}}, None))
        script = [[stale], [stalled], [good]]
        c._poll_results = lambda pending, timeout: (
            script.pop(0) if script else [])
        reports = {0: HostReport(host=0, procs=[]),
                   1: HostReport(host=1, procs=[])}
        results = c._await_results(99, reports, {0, 1})
        assert reports[0].ok and 0 in results
        assert reports[1].stalled and c._stalled[1] == 3
        # only the accepted success reached the lifetime ledger
        assert c._cum_chan == {"a->b": [1000.0, 2.0]}


def _host_farm_factory(workers):
    """A farm whose Collect folds on the host, item by item."""
    return DataParallelCollect(
        create=lambda i: jnp.asarray(float(i)),
        function=lambda x: x * x,
        collector=lambda a, x: a + float(x), init=0.0,
        workers=workers, jit_combine=False)


def _inside(inner, outer) -> bool:
    return (outer.ts <= inner.ts
            and inner.ts + inner.dur <= outer.ts + outer.dur)


class TestHostWaitSpans:
    def test_every_worker_host_records_park(self):
        net = _farm_factory(2)
        with ClusterDeployment(net, hosts=2, transport="inprocess",
                               microbatch_size=2, trace=True) as dep:
            dep.run(instances=6)
            dep.run(instances=6)
            ev = dep.merged_trace()
        parks = [e for e in ev if e.name == "park"]
        assert {e.host for e in parks} == {0, 1}
        # one park per batch posted to a host
        assert all(sum(e.host == h for e in parks) == 2 for h in (0, 1))
        assert all(e.kind == "span" and e.dur >= 0 for e in parks)

    def test_ready_and_fold_nest_inside_retire(self):
        net = _host_farm_factory(2)
        with ClusterDeployment(net, hosts=2, transport="inprocess",
                               microbatch_size=2, trace=True) as dep:
            out = dep.run(instances=7)
            ev = dep.merged_trace()
            collect_host = dep.plan.assignment["collect"]
        assert out["collect"] == sum(i * i for i in range(7))
        retires = {e.args["ci"]: e for e in ev
                   if e.name == "retire" and e.host == collect_host}
        readies = [e for e in ev
                   if e.name == "ready" and e.host == collect_host]
        folds = [e for e in ev if e.name == "fold"]
        assert {e.host for e in folds} == {collect_host}
        assert sorted(e.args["ci"] for e in folds) == [0, 1, 2, 3]
        assert sorted(e.args["ci"] for e in readies) == [0, 1, 2, 3]
        for f in folds:
            assert f.args["collect"] == "collect"
            # microbatches of 2 over 7 items: the last chunk holds one
            assert f.args["items"] == (1 if f.args["ci"] == 3 else 2)
        for e in readies + folds:
            assert _inside(e, retires[e.args["ci"]]), e
        ready_of = {e.args["ci"]: e for e in readies}
        for f in folds:
            r = ready_of[f.args["ci"]]
            assert r.ts + r.dur <= f.ts  # waiting, then folding
        conf = trace.check_conformance(net, ev)
        assert conf.ok and conf.coverage == 1.0, conf.detail

    def test_one_to_host_copy_per_chunk_inside_ready(self):
        net = _host_farm_factory(2)
        with ClusterDeployment(net, hosts=2, transport="inprocess",
                               microbatch_size=2, trace=True) as dep:
            out = dep.run(instances=7)
            ev = dep.merged_trace()
            collect_host = dep.plan.assignment["collect"]
        assert out["collect"] == sum(i * i for i in range(7))
        copies = [e for e in ev if e.name == "to_host"]
        assert {e.host for e in copies} == {collect_host}
        # exactly one per chunk of the one host fold
        assert sorted(e.args["ci"] for e in copies) == [0, 1, 2, 3]
        ready_of = {e.args["ci"]: e for e in ev
                    if e.name == "ready" and e.host == collect_host}
        for e in copies:
            assert e.kind == "span" and e.args["collect"] == "collect"
            assert _inside(e, ready_of[e.args["ci"]]), e
            # float32 items, microbatches of 2 over 7: the last holds one
            assert e.args["nbytes"] == 4 * (1 if e.args["ci"] == 3 else 2)

    def test_device_fold_records_no_to_host(self):
        net = _farm_factory(2)
        with ClusterDeployment(net, hosts=2, transport="inprocess",
                               microbatch_size=2, trace=True) as dep:
            dep.run(instances=7)
            ev = dep.merged_trace()
        assert [e for e in ev if e.name == "ready"]
        assert not [e for e in ev if e.name == "to_host"]

    def test_byte_counter_events_are_gone(self):
        net = _farm_factory(2)
        with ClusterDeployment(net, hosts=2, transport="inprocess",
                               microbatch_size=2, trace=True) as dep:
            dep.run(instances=8)
            ev = dep.merged_trace()
            rates = dep.metrics().bytes_per_s
        assert not [e for e in ev if e.name.startswith(
            ("sent_bytes:", "recv_bytes:"))]
        recvs = [e for e in ev if e.name == "recv"]
        assert recvs and all(e.args["nbytes"] > 0 for e in recvs)
        assert rates and all(v > 0 for v in rates.values())


def _two_stage_net():
    from repro.core import Collect, Emit, Network
    from repro.core.processes import Worker

    net = Network("ab")
    net.add(Emit(lambda i: jnp.asarray(float(i)), name="emit"),
            Worker(lambda x: x * x, name="a"),
            Worker(lambda x: x + 1.0, name="b"),
            Collect(lambda a, x: a + x, init=jnp.asarray(0.0),
                    jit_combine=True, name="collect"))
    return net


class TestProgramNames:
    @pytest.mark.parametrize("fuse, head, module", [
        (False, "a", "jit_stage_a"),
        (True, "a", "jit_stage_a_b"),
    ])
    def test_stage_jit_is_named_after_its_stage(self, fuse, head, module):
        from repro.core.stream import StreamExecutor

        ex = StreamExecutor(build(_two_stage_net()), microbatch_size=2,
                            fuse=fuse)
        text = ex._stage_jit(head, False).lower(jnp.zeros(2)).as_text()
        assert f"module @{module} " in text

    def test_carry_jit_is_named_after_its_collect(self):
        from repro.core.stream import StreamExecutor

        ex = StreamExecutor(build(_two_stage_net()), microbatch_size=2)
        text = ex._carry_jit("collect").lower(
            jnp.asarray(0.0), jnp.zeros(2)).as_text()
        assert "module @jit_carry_collect " in text


class TestSimGoldenTrace:
    def _one(self):
        """One no-fault sim deployment under per-host counting clocks."""
        trace.configure(clock="counting")
        try:
            net = _farm_factory(2)
            with ClusterDeployment(net, hosts=2,
                                   transport=SimTransport(),
                                   microbatch_size=2, trace=True,
                                   factory=(_farm_factory, (2,))) as dep:
                dep.run(instances=8)
                return dep.export_trace()
        finally:
            trace.configure(clock=None)

    def test_sim_export_byte_identical(self):
        """The deterministic-export contract (same discipline as
        test_netlog_snapshot): virtual clocks + sorted merge + sorted JSON
        keys make the sim's exported Chrome trace a pure function of the
        scenario."""
        a, b = self._one(), self._one()
        assert a == b
        doc = json.loads(a)
        assert len({e["pid"] for e in doc["traceEvents"]}) == 3


class TestControlPlaneSpans:
    def test_reconfigure_emits_epoch_bump(self):
        net = _farm_factory(2)
        with ClusterDeployment(net, hosts=2, transport="inprocess",
                               microbatch_size=2, trace=True) as dep:
            dep.run(instances=6)
            dep.reconfigure(hosts=1)
            names = [e.name for e in dep.merged_trace()
                     if e.host == "ctrl"]
            assert "reconfigure" in names
            assert "epoch_bump" in names
            assert names.count("batch") == 1
