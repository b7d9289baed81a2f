"""Cluster runtime: partitioning, transports, cross-host refinement.

The paper's capstone property — the same network runs unchanged on one
machine and on a cluster — plus the §6.1.1 refinement story lifted to
deployment: the partitioned network trace-refines the unpartitioned one
(checked both directions), and every transport reproduces the sequential
oracle bit-for-bit.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import (ClusterDeployment, ClusterError, CostProfile,
                           ExecConfig, InProcess, JaxMesh, MultiProcessPipe,
                           PartitionExecutor, ProcessCost, SharedMemoryRing,
                           abstract_partitioned_model, auto_assignment,
                           calibrate, check_redeployment, check_refinement,
                           cost_assignment, derive_cut_capacities,
                           make_transport, partition, repartition_without,
                           run_cluster)
from repro.core import (Collect, CombineNto1, DataParallelCollect, Emit,
                        GroupOfPipelineCollects, Network, NetworkError,
                        OnePipelineCollect, OneSeqCastList, Worker, build,
                        csp, netlog, run_sequential)
from repro.core.dataflow import Kind

_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
_SRC = os.path.join(_ROOT, "src")


def _sq(x):
    return x * x


def _inc(x):
    return x + 1.0


def _add(a, x):
    return a + x


def _mk_items(n):
    return lambda i: jnp.asarray(float(i))


def _farm(n=10, workers=3, **kw):
    return DataParallelCollect(create=_mk_items(n), function=_sq,
                               collector=_add, init=jnp.asarray(0.0),
                               workers=workers, jit_combine=True, **kw)


def _pipeline(n=7):
    return OnePipelineCollect(create=_mk_items(n), stage_ops=[_sq, _inc],
                              collector=_add, init=jnp.asarray(0.0),
                              jit_combine=True)


# module-level factory: the pipe transport's spawned hosts rebuild from this
def _farm_factory(n, workers):
    return DataParallelCollect(
        create=lambda i: jnp.asarray(float(i)),
        function=lambda x: x * x,
        collector=lambda a, x: a + x, init=jnp.asarray(0.0),
        workers=workers, jit_combine=True)


class TestPartitionPlanning:
    def test_auto_balanced_cut_farm(self):
        net = _farm()
        plan = partition(net, hosts=2)
        assert plan.hosts() == [0, 1]
        assert len(plan.cut) == 1
        (c,) = plan.cut
        assert len(net.successors(c.src)) == 1  # never cuts a fan
        # both partitions are legal GPP networks
        for h in plan.hosts():
            plan.subnetwork(h)

    def test_explicit_farm_branches_stay_with_spreader(self):
        net = _farm(9, 3, explicit=True)
        a = auto_assignment(net, 2)
        # every OneFanAny branch shares the spreader's host
        assert len({a[w] for w in net.successors("ofa")} | {a["ofa"]}) == 1

    def test_place_pins_override_auto(self):
        net = _pipeline()
        net.place("stage0", host=0).place("stage1", host=1)
        plan = partition(net, hosts=2)
        assert plan.assignment["stage0"] == 0
        assert plan.assignment["stage1"] == 1

    def test_place_validates(self):
        net = _pipeline()
        with pytest.raises(NetworkError, match="unknown process"):
            net.place("nope", host=0)
        with pytest.raises(NetworkError, match="host must be"):
            net.place("stage0", host=-1)

    def test_cyclic_host_graph_rejected(self):
        net = _pipeline()
        # emit..stage0 downstream of stage1 by host → host cycle 0<->1
        bad = {"emit": 1, "stage0": 1, "stage1": 0, "collect": 1}
        with pytest.raises(NetworkError, match="cyclic"):
            partition(net, assignment=bad)

    def test_fan_cut_rejected(self):
        net = _farm(9, 3, explicit=True)
        a = auto_assignment(net, 1)
        # split one branch off its spreader (downstream stays monotone so
        # the fan rule, not the cycle rule, must fire)
        for name in ("worker1", "afo", "collect"):
            a[name] = 1
        with pytest.raises(NetworkError, match="fans out"):
            partition(net, assignment=a)

    def test_missing_process_rejected(self):
        net = _pipeline()
        with pytest.raises(NetworkError, match="no host for"):
            partition(net, assignment={"emit": 0})

    def test_single_host_plan_has_no_cut(self):
        plan = partition(_farm(), hosts=1)
        assert plan.cut == [] and plan.hosts() == [0]


class TestCutRefinement:
    """core/csp.py across a partition cut: the partitioned model and the
    original refine each other — the paper's ``[T=`` in BOTH directions."""

    def test_farm_cut_refines_both_directions(self):
        net = _farm()
        plan = partition(net, hosts=2)
        part = abstract_partitioned_model(net, plan)
        assert csp.trace_equivalent(part, net, instances=3)  # part [T= net
        assert csp.trace_equivalent(net, part, instances=3)  # net [T= part

    def test_pipeline_cut_refines_both_directions(self):
        net = _pipeline()
        plan = partition(net, hosts=2)
        part = abstract_partitioned_model(net, plan)
        assert csp.trace_equivalent(part, net, instances=3)
        assert csp.trace_equivalent(net, part, instances=3)

    def test_check_refinement_wraps_both(self):
        net = _pipeline()
        assert check_refinement(net, partition(net, hosts=2))

    def test_relay_model_is_safe(self):
        """CSPm Definition 6 for the partitioned model itself."""
        net = _farm()
        part = abstract_partitioned_model(net, partition(net, hosts=2))
        r = csp.check(part, instances=3)
        assert r.deadlock_free and r.divergence_free
        assert r.all_paths_terminate and r.deterministic

    def test_three_way_cut_refines(self):
        net = _pipeline()
        plan = partition(net, hosts=3)
        assert len(plan.cut) >= 2
        assert check_refinement(net, plan)


class TestInProcessCluster:
    """Thread hosts, queue channels: results ≡ sequential oracle."""

    @pytest.mark.parametrize("hosts,mb", [(2, 3), (2, 4), (3, 2)])
    def test_farm_bit_identical(self, hosts, mb):
        net = _farm()
        seq = run_sequential(net, 10)["collect"]
        out = run_cluster(net, instances=10, hosts=hosts,
                          microbatch_size=mb)
        assert float(out["collect"]) == float(seq)
        assert all(r.ok for r in out.reports)

    def test_pipeline_uneven_chunks(self):
        net = _pipeline()
        seq = run_sequential(net, 7)["collect"]
        out = run_cluster(net, instances=7, hosts=2, microbatch_size=3)
        assert float(out["collect"]) == float(seq)

    def test_gop_composite(self):
        net = GroupOfPipelineCollects(
            create=_mk_items(12), stage_ops=[_sq, _inc, _inc],
            collector=_add, init=jnp.asarray(0.0), jit_combine=True,
            groups=3)
        seq = run_sequential(net, 12)["collect"]
        out = run_cluster(net, instances=12, hosts=2, microbatch_size=4)
        assert float(out["collect"]) == float(seq)

    def test_host_side_dict_collector(self):
        net = DataParallelCollect(
            create=_mk_items(5), function=_sq,
            collector=lambda acc, x: {**acc, len(acc): float(x)},
            init={}, workers=2, jit_combine=False)
        out = run_cluster(net, instances=5, hosts=2, microbatch_size=2)
        assert out["collect"] == {i: float(i * i) for i in range(5)}

    def test_combine_reducer_across_cut(self):
        """COMBINE emits nothing until its final chunk: SKIP markers keep
        the cut channel chunk-aligned."""
        vals = jnp.asarray(np.arange(12, dtype=np.float32))
        net = Network("comb")
        net.add(Emit(lambda i: vals[i], name="emit"),
                OneSeqCastList(name="cast"))
        for w in range(2):
            net.procs[f"w{w}"] = Worker(_sq if w == 0 else _inc,
                                        name=f"w{w}", tag=f"f{w}")
            net.connect("cast", f"w{w}")
        net.procs["comb"] = CombineNto1(lambda a, b: a + b, name="comb")
        net.connect("w0", "comb")
        net.connect("w1", "comb")
        net._tail = "comb"
        net.add(Collect(_add, init=jnp.asarray(0.0), jit_combine=True,
                        name="collect"))
        # cut between the combine and the collect: every chunk but the last
        # ships a SKIP marker
        assignment = {n: 0 for n in net.procs}
        assignment["collect"] = 1
        plan = partition(net, assignment=assignment)
        assert [(c.src, c.dst) for c in plan.cut] == [("comb", "collect")]
        cn = build(net)
        fused_like = cn.run_streaming(instances=12, microbatch_size=5)
        out = run_cluster(net, instances=12, plan=plan, microbatch_size=5)
        assert float(out["collect"]) == float(fused_like["collect"])

    def test_capacity_bounds_transport_queue(self):
        """ChannelDef.capacity flows across the transport: the cut channel's
        FIFO is exactly that deep (cross-host backpressure)."""
        net = Network("capped")
        net.add(Emit(_mk_items(8), name="emit"), Worker(_sq, name="w"))
        net.procs["collect"] = Collect(_add, init=jnp.asarray(0.0),
                                       jit_combine=True, name="collect")
        net.connect("w", "collect", capacity=1)
        plan = partition(net, assignment={"emit": 0, "w": 0, "collect": 1})
        t = InProcess()
        out = run_cluster(net, instances=8, plan=plan, transport=t,
                          microbatch_size=2)
        assert float(out["collect"]) == float(sum(i ** 2 for i in range(8)))
        assert t._queues[("w", "collect")].maxsize == 1

    def test_results_carry_reports(self):
        out = run_cluster(_farm(), instances=10, hosts=2, microbatch_size=5)
        assert {r.host for r in out.reports} == {0, 1}
        assert all("stream:" in r.stats_summary for r in out.reports)
        assert all("donation" in r.donation_summary for r in out.reports)


class TestDerivedCapacities:
    """Satellite: default cut-channel FIFO depth comes from the consumer
    executor's depth/lane appetite, not a blind constant, and the chosen
    values land in HostReport.capacities."""

    def test_explicit_capacity_wins(self):
        net = Network("capped")
        net.add(Emit(_mk_items(8), name="emit"), Worker(_sq, name="w"))
        net.procs["collect"] = Collect(_add, init=jnp.asarray(0.0),
                                       jit_combine=True, name="collect")
        net.connect("w", "collect", capacity=1)
        plan = partition(net, assignment={"emit": 0, "w": 0, "collect": 1})
        caps = derive_cut_capacities(plan, ExecConfig())
        assert caps[("w", "collect")] == 1

    def test_default_derived_from_depth_and_lanes(self):
        net = _farm()
        plan = partition(net, hosts=2)
        (c,) = plan.cut
        from repro.core.stream import plan_depth_lanes
        sub = plan.subnetwork(plan.assignment[c.dst])
        depth, lanes = plan_depth_lanes(sub, None, None)
        caps = derive_cut_capacities(plan, ExecConfig())
        assert caps[(c.src, c.dst)] == max(2, depth, lanes)
        # a deeper in-flight appetite widens the derived FIFO
        deep = derive_cut_capacities(plan, ExecConfig(max_in_flight=7))
        assert deep[(c.src, c.dst)] == 7

    def test_reports_and_netlog_carry_capacities(self):
        net = _farm()
        plan = partition(net, hosts=2)
        out = run_cluster(net, instances=10, plan=plan, microbatch_size=5)
        merged = {}
        for r in out.reports:
            merged.update(r.capacities)
        (c,) = plan.cut
        key = f"{c.src}->{c.dst}"
        assert key in merged and merged[key] >= 2
        rep = netlog.cluster_report(plan, out.reports)
        assert f"capacity={merged[key]}" in rep

    def test_transport_fifo_sized_to_derived(self):
        net = _farm()
        plan = partition(net, hosts=2)
        t = InProcess()
        run_cluster(net, instances=10, plan=plan, transport=t,
                    microbatch_size=5)
        (c,) = plan.cut
        caps = derive_cut_capacities(plan, ExecConfig(microbatch_size=5))
        assert t._queues[(c.src, c.dst)].maxsize == caps[(c.src, c.dst)]

    def test_fan_immediately_at_cut_boundary(self):
        """Satellite edge case: when the cut channel feeds straight into a
        work-stealing fan, the derived FIFO depth must cover the fan's full
        lane appetite, not just the channel-capacity default."""
        net = _farm(12, 4, explicit=True)  # explicit OneFanAny, 4 branches
        assignment = {n: (0 if n == "emit" else 1) for n in net.procs}
        plan = partition(net, assignment=assignment)
        (c,) = plan.cut
        assert net.procs[c.dst].kind is Kind.SPREADER  # fan AT the boundary
        caps = derive_cut_capacities(plan, ExecConfig())
        from repro.core.stream import plan_depth_lanes
        depth, lanes = plan_depth_lanes(plan.subnetwork(1), None, None)
        assert lanes == 4  # the fan defines the lane count
        assert caps[(c.src, c.dst)] == max(2, depth, lanes) >= 4
        # and the real deployment matches the oracle with that sizing
        out = run_cluster(net, instances=12, plan=plan, microbatch_size=4)
        assert float(out["collect"]) == float(
            run_sequential(net, 12)["collect"])

    def test_single_process_partitions(self):
        """Satellite edge case: one process per host — every subnet is a
        lone stage between shims, and every cut still gets the >= 2 floor."""
        net = _pipeline()
        order = net.toposort()
        plan = partition(net, assignment={n: i for i, n in enumerate(order)})
        assert len(plan.cut) == len(order) - 1
        caps = derive_cut_capacities(plan, ExecConfig())
        assert all(v >= 2 for v in caps.values())
        out = run_cluster(net, instances=7, plan=plan, microbatch_size=3)
        assert float(out["collect"]) == float(
            run_sequential(net, 7)["collect"])

    def test_capacity_floor_with_depth_one_consumer(self):
        """Satellite edge case: a consumer executor throttled to depth 1
        must still get the DEFAULT_CAPACITY floor — a 1-deep transport FIFO
        would serialise producer and consumer chunk-by-chunk."""
        from repro.cluster.transport import DEFAULT_CAPACITY
        net = _farm()
        plan = partition(net, hosts=2)
        (c,) = plan.cut
        caps = derive_cut_capacities(plan, ExecConfig(max_in_flight=1,
                                                      lanes=1))
        assert caps[(c.src, c.dst)] == DEFAULT_CAPACITY == 2

    def test_coalesced_capacity_degrades_to_uncoalesced_floor(self):
        """Satellite edge case: records larger than the coalesce budget
        ship one per slot, so the channel must get exactly the uncoalesced
        sizing ``max(floor, depth, lanes)`` — the degraded case once
        dropped the transport's floor and shrank large-record FIFOs."""
        from repro.core.stream import coalesced_capacity
        # per_slot == 1: budget smaller than one record
        assert coalesced_capacity(1, 1, record_bytes=4096,
                                  coalesce_bytes=64, floor=4) == 4
        assert coalesced_capacity(6, 3, record_bytes=4096,
                                  coalesce_bytes=64, floor=4) == 6
        # genuine coalescing still shrinks proportionally (floor unused)
        assert coalesced_capacity(8, 1, record_bytes=64,
                                  coalesce_bytes=256, floor=4) == 2

    def test_derived_capacities_floor_under_coalescing(self):
        """With coalescing on but a cut whose records exceed the budget,
        the derived FIFO must match what the per-record path would get."""
        from repro.cluster.costs import CostProfile, ProcessCost
        net = _farm()
        plan = partition(net, hosts=2)
        (c,) = plan.cut
        profile = CostProfile(costs={c.src: ProcessCost(
            name=c.src, out_bytes=1 << 20)})  # 1 MiB records
        cfg = ExecConfig(max_in_flight=1, lanes=1,
                         coalesce_bytes=1 << 10,  # far below one record
                         profile=profile)
        plain = derive_cut_capacities(plan, ExecConfig(max_in_flight=1,
                                                       lanes=1))
        assert derive_cut_capacities(plan, cfg, profile=profile) == plain


class TestClusterDeployment:
    """Tentpole: a deployment partitions, compiles, and spawns ONCE; warm
    `.run` calls reuse everything and stay bit-identical to the oracle."""

    def test_three_batches_bit_identical(self):
        net = _farm()
        with ClusterDeployment(net, hosts=2, microbatch_size=2) as dep:
            for n in (4, 6, 10):
                out = dep.run(instances=n)
                seq = run_sequential(net, n)["collect"]
                assert float(out["collect"]) == float(seq)
                assert all(r.ok for r in out.reports)

    def test_stage_jits_compile_exactly_once(self):
        """Compile-counter hook: the first batch traces every stage jit;
        same-shape warm batches must trace (and build) nothing."""
        net = _farm()
        with ClusterDeployment(net, hosts=2, microbatch_size=2) as dep:
            out1 = dep.run(instances=4)
            assert sum(r.jit_builds for r in out1.reports) > 0
            traces = {h: dict(ex.trace_counts)
                      for h, ex in dep.executors.items()}
            built = []
            for ex in dep.executors.values():
                ex.on_jit_build = built.append
            for n in (4, 6, 8):  # mb=2: every chunk shape already traced
                out = dep.run(instances=n)
                assert sum(r.jit_builds for r in out.reports) == 0
            assert built == []
            for h, ex in dep.executors.items():
                assert ex.trace_counts == traces[h]
            # a NEW chunk shape is honestly reported as a retrace even
            # though every jit cache key already exists
            out = dep.run(instances=5)  # last chunk has fresh shape (1,)
            assert sum(r.jit_builds for r in out.reports) > 0

    def test_explicit_batch_pytree(self):
        """deployment.run(batch=...) feeds the Emit an explicit batch."""
        net = _farm()
        vals = jnp.asarray(np.arange(8, dtype=np.float32) + 100.0)
        with ClusterDeployment(net, hosts=2, microbatch_size=2) as dep:
            out = dep.run(batch=vals)
            expect = float(jnp.sum(vals * vals))
            assert float(out["collect"]) == expect
            # and instance-driven batches still work on the same deployment
            seq = run_sequential(net, 6)["collect"]
            assert float(dep.run(instances=6)["collect"]) == float(seq)

    def test_failure_on_batch2_reports_then_same_deployment_recovers(self):
        """A host failure mid-deployment still yields the §8 cluster report,
        but the deployment is no longer poisoned: the next plain run()
        auto-recovers (epoch bump, drained transport) and streams a new
        batch through the SAME warm deployment.  A deterministic poison
        batch keeps failing precisely (never limps on) — recovery repairs
        hosts, not user code."""
        def tripwire(acc, x):
            if float(x) >= 16.0:
                raise RuntimeError("collector tripped")
            return {**acc, len(acc): float(x)}

        net = DataParallelCollect(create=_mk_items(8), function=_sq,
                                  collector=tripwire, init={}, workers=2,
                                  jit_combine=False)
        with ClusterDeployment(net, hosts=2, microbatch_size=2,
                               timeout_s=60) as dep:
            out = dep.run(instances=4)  # squares < 16: fine
            assert all(r.ok for r in out.reports)
            with pytest.raises(ClusterError) as ei:
                dep.run(instances=8)  # 5² = 25 trips the collector
            assert "collector tripped" in str(ei.value)
            assert "FAILED" in str(ei.value)
            # NOT poisoned: a fresh batch runs on the same deployment
            # (auto-recovery bumps the epoch first)
            out = dep.run(instances=4)
            assert out["collect"] == {i: float(i * i) for i in range(4)}
            assert dep.epoch == 2 and len(dep.events) == 1
            # the poison batch itself still fails — deterministically
            with pytest.raises(ClusterError):
                dep.run(instances=8)
            with pytest.raises(ClusterError):
                dep.recover()  # the replay trips the same user bug
            assert dep.run(instances=4)["collect"] == \
                {i: float(i * i) for i in range(4)}

    def test_closed_deployment_refuses(self):
        dep = ClusterDeployment(_farm(), hosts=2, microbatch_size=2)
        dep.close()
        with pytest.raises(NetworkError, match="closed"):
            dep.run(instances=4)

    def test_process_transport_requires_factory(self):
        """Refused before the transport allocates anything: a failed start
        must not leak shm segments or queue feeder threads (regression)."""
        for tname in ("pipe", "shm"):
            t = make_transport(tname)
            with pytest.raises(NetworkError, match="factory"):
                with ClusterDeployment(_farm(), hosts=2,
                                       transport=t) as dep:
                    dep.run(instances=4)
            if tname == "shm":
                assert not t._owned and not t._rings
            else:
                assert not t._queues

    def test_pipe_deployment_reuse_over_real_processes(self):
        net = _farm_factory(10, 3)
        with ClusterDeployment(net, hosts=2, transport="pipe",
                               microbatch_size=2,
                               factory=(_farm_factory, (10, 3))) as dep:
            for n in (4, 10):
                out = dep.run(instances=n)
                seq = run_sequential(net, n)["collect"]
                assert float(out["collect"]) == float(seq)
            warm = dep.run(instances=10)
            assert sum(r.jit_builds for r in warm.reports) == 0

    def test_shm_deployment_reuse_over_real_processes(self):
        net = _farm_factory(10, 3)
        with ClusterDeployment(net, hosts=2, transport="shm",
                               microbatch_size=2,
                               factory=(_farm_factory, (10, 3))) as dep:
            seq = run_sequential(net, 10)["collect"]
            for _ in range(2):
                out = dep.run(instances=10)
                assert float(out["collect"]) == float(seq)
            assert sum(r.jit_builds for r in out.reports) == 0


class TestFailureCapture:
    def test_worker_failure_surfaces_cross_host(self):
        def boom(x):
            raise RuntimeError("worker exploded")

        net = DataParallelCollect(create=_mk_items(4), function=boom,
                                  collector=_add, init=jnp.asarray(0.0),
                                  workers=2, jit_combine=True)
        with pytest.raises(ClusterError) as ei:
            run_cluster(net, instances=4, hosts=2, microbatch_size=2,
                        timeout_s=60)
        err = ei.value
        # the netlog cluster report carries the failing host's traceback
        assert "worker exploded" in str(err)
        assert "FAILED" in str(err)
        failed = [r for r in err.reports if not r.ok]
        assert failed and any("worker exploded" in (r.error or "")
                              for r in failed)

    def test_cluster_report_renders_ok_hosts(self):
        net = _farm()
        plan = partition(net, hosts=2)
        out = run_cluster(net, instances=10, plan=plan, microbatch_size=5)
        rep = netlog.cluster_report(plan, out.reports)
        assert "host 0 [ok]" in rep and "host 1 [ok]" in rep
        assert "channel" in rep


class TestMultiProcessPipe:
    """Real OS-process hosts (spawned interpreters): the CI-grade boundary."""

    def test_farm_bit_identical_over_real_processes(self):
        net = _farm_factory(10, 3)
        seq = run_sequential(net, 10)["collect"]
        out = run_cluster(net, instances=10, hosts=2, transport="pipe",
                          microbatch_size=3,
                          factory=(_farm_factory, (10, 3)))
        assert float(out["collect"]) == float(seq)
        assert all(r.ok for r in out.reports)

    def test_pipe_requires_factory(self):
        with pytest.raises(NetworkError, match="factory"):
            run_cluster(_farm(), instances=4, hosts=2, transport="pipe",
                        microbatch_size=2)

    @pytest.mark.parametrize("kind", ["pipe", "shm"])
    def test_process_hosts_refused_on_an_accelerator(self, monkeypatch,
                                                     kind):
        """A process holding an accelerator spawns no host process that
        would contend for it (or quietly compute on the CPU instead): the
        process-host transports refuse and name the thread-host ones."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(NetworkError,
                           match="holds the tpu device.*'inprocess'.*"
                                 "'jaxmesh'"):
            run_cluster(_farm_factory(4, 2), instances=4, hosts=2,
                        transport=kind, microbatch_size=2,
                        factory=(_farm_factory, (4, 2)))
        for thread_hosts in ("inprocess", "jaxmesh"):
            make_transport(thread_hosts).close()

    def test_encode_roundtrip(self):
        from repro.cluster.transport import decode, encode
        tree = (jnp.asarray([1.0, 2.0]), {"a": jnp.arange(3)})
        enc = encode(tree)
        assert all(isinstance(l, np.ndarray)
                   for l in jax.tree_util.tree_leaves(enc))
        dec = decode(enc)
        np.testing.assert_array_equal(dec[0], np.asarray([1.0, 2.0]))

    def test_pack_raw_preserves_dtype_endianness_and_0d(self):
        """Satellite hardening: the raw header+buffer encoding that crosses
        process boundaries must round-trip dtype (byte order included),
        0-d arrays, bools, and non-contiguous views, bit-for-bit."""
        from repro.cluster.transport import _RawLeaf, pack_raw, unpack_raw
        tree = {
            "big": np.arange(6, dtype=">f4").reshape(2, 3),
            "little": np.arange(6, dtype="<i2"),
            "zerod": np.float64(3.25),
            "bool": np.asarray([True, False, True]),
            "noncontig": np.arange(12.0).reshape(3, 4).T,
            "jax": jnp.asarray([1.5, -2.5]),
            "empty": np.zeros((0, 4), np.int32),
        }
        packed = pack_raw(tree)
        # every plain leaf became a raw header+buffer record, not an array
        assert all(isinstance(l, _RawLeaf)
                   for l in jax.tree_util.tree_leaves(packed))
        dec = unpack_raw(packed)
        for k, v in tree.items():
            a = np.asarray(v)
            assert dec[k].dtype == a.dtype, k
            assert dec[k].shape == a.shape, k
            assert dec[k].tobytes() == np.ascontiguousarray(a).tobytes(), k

    def test_unpack_raw_arrays_are_writable(self):
        """The pickle path this encoding replaces handed out writable
        arrays; consumers that mutate received chunks must keep working
        (regression)."""
        from repro.cluster.transport import pack_raw, unpack_raw
        out = unpack_raw(pack_raw({"x": np.arange(4.0)}))
        out["x"] *= 2.0  # raises ValueError if read-only
        np.testing.assert_array_equal(out["x"], [0.0, 2.0, 4.0, 6.0])

    def test_pack_raw_markers_and_exotic_dtypes_pass_through(self):
        from repro.cluster.transport import EOS, SKIP, pack_raw, unpack_raw
        assert pack_raw(SKIP) == SKIP and unpack_raw(EOS) == EOS
        structured = np.zeros(2, dtype=[("a", "<f4"), ("b", "<i8")])
        packed = pack_raw(structured)  # pickle fallback keeps the array
        assert isinstance(packed, np.ndarray)
        np.testing.assert_array_equal(unpack_raw(packed), structured)

    def test_pipe_pack_roundtrip_through_endpoint(self):
        """The _pack/_unpack pair a pipe endpoint actually applies."""
        from repro.cluster.transport import _PipeEndpoint
        ep = _PipeEndpoint({})
        tree = {"x": np.arange(4, dtype=">u2"), "y": jnp.float32(7.0)}
        out = ep._unpack(ep._pack(tree))
        assert out["x"].dtype == np.dtype(">u2")
        np.testing.assert_array_equal(out["x"], np.arange(4, dtype=">u2"))
        assert np.asarray(out["y"]).shape == ()
        assert np.asarray(out["y"]).dtype == np.float32

    def test_encode_result_preserves_0d_and_dtype(self):
        from repro.cluster.runtime import _encode_result
        out = _encode_result({"collect": jnp.asarray(5, jnp.int32),
                              "v": jnp.asarray([1.0, 2.0])})
        assert np.asarray(out["collect"]).shape == ()
        assert np.asarray(out["collect"]).dtype == np.int32


class TestSharedMemoryRing:
    """Zero-copy slot-ring transport: payloads cross as raw buffer writes."""

    def test_farm_bit_identical_over_shm(self):
        net = _farm_factory(10, 3)
        seq = run_sequential(net, 10)["collect"]
        out = run_cluster(net, instances=10, hosts=2, transport="shm",
                          microbatch_size=3,
                          factory=(_farm_factory, (10, 3)))
        assert float(out["collect"]) == float(seq)
        assert all(r.ok for r in out.reports)

    def test_ring_send_recv_in_process(self):
        t = SharedMemoryRing(slot_bytes=1 << 12)
        try:
            t.setup([("a", "b")], {("a", "b"): 2})
            val = {"x": np.arange(8, dtype="<f8"), "y": np.float32(7)}
            t.send(("a", "b"), 0, val)
            out = t.recv(("a", "b"), 0)
            np.testing.assert_array_equal(out["x"], val["x"])
            assert np.asarray(out["y"]).shape == ()
            # slot came back: the ring can carry more chunks than slots
            for ci in (1, 2, 3):
                t.send(("a", "b"), ci, val)
                np.testing.assert_array_equal(
                    t.recv(("a", "b"), ci)["x"], val["x"])
        finally:
            t.close()

    def test_oversize_chunk_falls_back_inline(self):
        t = SharedMemoryRing(slot_bytes=128)
        try:
            t.setup([("a", "b")], {("a", "b"): 2})
            big = np.arange(1024, dtype=np.float64)
            t.send(("a", "b"), 0, big)
            np.testing.assert_array_equal(t.recv(("a", "b"), 0), big)
        finally:
            t.close()

    def test_ring_capacity_is_slot_count(self):
        t = SharedMemoryRing(slot_bytes=1 << 10)
        try:
            t.setup([("a", "b")], {("a", "b"): 3})
            ring = t._rings[("a", "b")]
            assert len(ring.slot_names) == 3
            assert ring.data_q._maxsize == 3
        finally:
            t.close()

    def test_out_of_order_detected_and_slot_recycled(self):
        from repro.cluster.transport import TransportError
        t = SharedMemoryRing(slot_bytes=1 << 10)
        try:
            t.setup([("a", "b")], {("a", "b"): 2})
            t.send(("a", "b"), 5, np.arange(3.0))
            with pytest.raises(TransportError, match="out of order"):
                t.recv(("a", "b"), 0)
            # the offending chunk's slot went back to the ring (invariant:
            # free slots + in-flight slots == capacity, here 2 + 0)
            ring = t._rings[("a", "b")]
            assert ring.free_q.qsize() == 2
        finally:
            t.close()


def _trip_once_farm(trip_at: int, state: dict):
    """Farm whose host-side collector raises exactly once, on its
    ``trip_at``-th call ever — a transient host failure (thread hosts share
    ``state`` with the test)."""
    def coll(acc, x):
        state["n"] = state.get("n", 0) + 1
        if state["n"] == trip_at:
            raise RuntimeError("transient collector failure")
        return {**acc, len(acc): float(x)}

    return DataParallelCollect(create=_mk_items(8), function=_sq,
                               collector=coll, init={}, workers=2,
                               jit_combine=False)


class TestElasticRecovery:
    """Tentpole: a live deployment is a control plane — host failures are
    drained, repaired (restart or rebalance), epoch-stamped, re-proved, and
    the failed batch's lost chunks replayed, all without a fresh start()."""

    EXPECT8 = {i: float(i * i) for i in range(8)}

    def test_recover_replays_and_unaffected_hosts_stay_warm(self):
        state: dict = {}
        net = _trip_once_farm(trip_at=12, state=state)
        with ClusterDeployment(net, hosts=2, microbatch_size=2,
                               timeout_s=60) as dep:
            assert dep.run(instances=8)["collect"] == self.EXPECT8
            traces = {h: dict(ex.trace_counts)
                      for h, ex in dep.executors.items()}
            with pytest.raises(ClusterError):
                dep.run(instances=8)  # call 12 lands mid-batch-2
            rec = dep.recover()
            # the replayed batch is bit-identical to the oracle
            assert rec["collect"] == self.EXPECT8
            assert all(r.ok for r in rec.reports)
            # zero new stage jits anywhere: recovery reused every warm
            # executor (same shapes, same jits — compile-counter asserted)
            assert sum(r.jit_builds for r in rec.reports) == 0
            for h, ex in dep.executors.items():
                assert dict(ex.trace_counts) == traces[h]
            # epoch bumped, event recorded, refinement re-proved
            assert dep.epoch == 2 and rec.epoch == 2
            (ev,) = dep.events
            assert ev.epoch_from == 1 and ev.epoch_to == 2
            assert ev.erred == [1] and ev.refined is True
            # ... and the deployment keeps serving warm batches
            out = dep.run(instances=8)
            assert out["collect"] == self.EXPECT8
            assert sum(r.jit_builds for r in out.reports) == 0

    def test_recovery_section_in_cluster_report(self):
        state: dict = {}
        net = _trip_once_farm(trip_at=12, state=state)
        with ClusterDeployment(net, hosts=2, microbatch_size=2,
                               timeout_s=60) as dep:
            dep.run(instances=8)
            with pytest.raises(ClusterError):
                dep.run(instances=8)
            rec = dep.recover()
            rep = netlog.cluster_report(dep.plan, rec.reports,
                                        events=dep.events)
            assert "plan epoch 2" in rep
            assert "-- recovery --" in rep
            assert "epoch 1 -> 2 (restart)" in rep
            assert "refinement(epoch 2)=True" in rep

    def test_rebalance_moves_processes_onto_survivors(self):
        """recover(mode="rebalance") reuses the planner: the failed host's
        processes move to survivors, the new plan is validated and
        re-proved, and the replay runs on the new topology."""
        state: dict = {}
        net = _trip_once_farm(trip_at=12, state=state)
        with ClusterDeployment(net, hosts=2, microbatch_size=2,
                               timeout_s=60) as dep:
            assert dep.run(instances=8)["collect"] == self.EXPECT8
            old_hosts = dep.plan.hosts()
            assert old_hosts == [0, 1]
            with pytest.raises(ClusterError):
                dep.run(instances=8)
            rec = dep.recover(mode="rebalance")
            assert rec["collect"] == self.EXPECT8
            # the erred host was evacuated: its procs now live on host 0
            assert dep.plan.hosts() == [0]
            (ev,) = dep.events
            assert ev.mode == "rebalance" and ev.moved
            assert all(dst == 0 for _, dst in ev.moved.values())
            assert ev.refined is True  # epoch-2 plan [T=] original net
            # the rebalanced single-host deployment keeps serving
            assert dep.run(instances=8)["collect"] == self.EXPECT8

    def test_stalled_survivor_resumes_partial_fold(self):
        """A consumer whose producer dies mid-stream stalls with its fold
        intact (chunk-replay bookkeeping): resuming replays ONLY the lost
        chunks, and the result matches the uninterrupted oracle."""
        from repro.cluster.transport import EOS as _EOS
        net = _farm()
        plan = partition(net, hosts=2)
        (c,) = plan.cut
        consumer = plan.assignment[c.dst]
        chan = (c.src, c.dst)
        oracle = run_sequential(net, 8)["collect"]

        t = InProcess()
        t.setup([chan], {chan: 8})
        from repro.core.builder import build as _build
        ex = PartitionExecutor(_build(plan.subnetwork(consumer)), plan=plan,
                               host=consumer, endpoint=t, microbatch_size=2)
        producer_ex = PartitionExecutor(
            _build(plan.subnetwork(plan.assignment[c.src])), plan=plan,
            host=plan.assignment[c.src], endpoint=t, microbatch_size=2)
        bounds = [(0, 2), (2, 4), (4, 6), (6, 8)]
        from repro.core.builder import make_emit_batch
        batch = make_emit_batch(net, 8)
        # producer streams chunks 0..1, then "dies" (EOS on the wire)
        producer_ex.run_partition(bounds[:2], batch)
        t.send(chan, -1, _EOS)
        with pytest.raises(NetworkError):
            ex.run_partition(bounds)
        st = ex.replay_state
        assert st is not None and st.next_ci == 2
        assert ex.stats.summary()  # telemetry survives the interruption
        # "controller": bump the epoch, replay the tail from the restarted
        # producer, resume the survivor — only chunks 2..3 flow again
        t.set_epoch(2)
        producer_ex.reset_run_state()
        producer_ex.run_partition(bounds, batch, start_ci=2)
        out = ex.resume_partition()
        assert float(out["collect"]) == float(oracle)
        assert ex.stats.replays == 1 and ex.stats.resumed_at == 2

    def test_transport_epoch_and_duplicate_semantics(self):
        """Stale-epoch records and replayed duplicates are dropped; future
        epochs are a protocol error; EOS outranks ordering."""
        from repro.cluster.transport import TransportError
        t = InProcess()
        t.setup([("a", "b")], {("a", "b"): 8})
        t.send(("a", "b"), 0, "old-epoch")
        t.set_epoch(2)
        t.send(("a", "b"), 0, "dup")       # will be asked for as ci=1
        t.send(("a", "b"), 1, "current")
        # epoch-1 record dropped, ci=0 duplicate dropped, ci=1 delivered
        assert t.recv(("a", "b"), 1) == "current"
        t.epoch = 1  # consumer behind the controller: future-epoch error
        t.send(("a", "b"), 2, "future")  # sent at epoch 1...
        t.set_epoch(2)
        t._queues[("a", "b")].put((3, 2, "from-the-future"))
        with pytest.raises(TransportError, match="epoch"):
            t.recv(("a", "b"), 2)

    def test_drain_keep_and_requeue(self):
        """drain() empties the FIFOs, returning undelivered chunks for kept
        channels; requeue() re-stamps them under the new epoch so a stalled
        survivor accepts exactly what it never folded."""
        t = InProcess()
        t.setup([("a", "b"), ("c", "d")], {("a", "b"): 8, ("c", "d"): 8})
        for ci in (2, 3, 4):
            t.send(("a", "b"), ci, {"v": np.asarray([ci])})
        t.send(("c", "d"), 0, "doomed")
        drained = t.drain(keep={("a", "b")})
        assert [ci for ci, _ in drained[("a", "b")][0]] == [2, 3, 4]
        assert drained[("c", "d")] == ([], 1)
        t.set_epoch(2)
        n = t.requeue(("a", "b"), drained[("a", "b")][0])
        assert n == 3
        for ci in (2, 3, 4):  # consumer at the new epoch reads them in order
            assert int(t.recv(("a", "b"), ci)["v"][0]) == ci

    def test_shm_drain_recycles_slots(self):
        t = SharedMemoryRing(slot_bytes=1 << 10)
        try:
            t.setup([("a", "b")], {("a", "b"): 3})
            for ci in range(3):
                t.send(("a", "b"), ci, np.arange(4.0))
            drained = t.drain()  # no keep: discard everything
            assert drained[("a", "b")][1] == 3
            # every slot is back on the free ring
            assert t._rings[("a", "b")].free_q.qsize() == 3
        finally:
            t.close()

    def test_shm_atexit_unlink_registered(self):
        """Satellite: owned segments unlink from atexit, not only close()
        — a parent that dies mid-batch must not strand /dev/shm segments."""
        t = SharedMemoryRing(slot_bytes=1 << 10)
        t.setup([("a", "b")], {("a", "b"): 2})
        assert t._atexit_armed
        names = [s.name for slots in t._owned.values() for s in slots]
        t._unlink_owned()  # what atexit would run
        from multiprocessing import shared_memory
        for name in names:
            with pytest.raises(FileNotFoundError):
                shared_memory.SharedMemory(name=name)
        t.close()  # idempotent after the atexit path
        assert not t._atexit_armed

    def test_repartition_without_prefers_upstream_merge(self):
        net = _pipeline()
        net.place("emit", host=0).place("stage0", host=1)
        net.place("stage1", host=2).place("collect", host=2)
        plan = partition(net, hosts=3)
        assign = repartition_without(plan, [1])
        assert assign["stage0"] == 0  # merged into the upstream survivor
        partition(net, assignment=assign)  # validates
        assert check_redeployment(net, plan,
                                  partition(net, assignment=assign))

    def test_repartition_without_all_hosts_failed(self):
        net = _farm()
        plan = partition(net, hosts=2)
        with pytest.raises(NetworkError, match="every host failed"):
            repartition_without(plan, plan.hosts())

    def test_check_redeployment_across_plan_shapes(self):
        net = _farm()
        p2 = partition(net, hosts=2)
        for hosts in (1, 3):
            assert check_redeployment(net, p2, partition(net, hosts=hosts))

    def test_plain_run_after_failure_discards_undelivered_chunks(self):
        """Auto-recovery (run() after a failure, no replay) must DISCARD the
        failed stream's undelivered chunks rather than requeue them: a fresh
        batch's consumer expects chunk 0, and a requeued chunk 2 would trip
        the out-of-order protocol check (regression)."""
        from repro.cluster.transport import SKIP
        state: dict = {}
        net = _trip_once_farm(trip_at=12, state=state)
        with ClusterDeployment(net, hosts=2, microbatch_size=2,
                               timeout_s=60) as dep:
            assert dep.run(instances=8)["collect"] == self.EXPECT8
            with pytest.raises(ClusterError):
                dep.run(instances=8)
            # pretend the failed stream left undelivered chunks bound for a
            # stalled survivor (the kill-host scenario, made deterministic)
            ctrl = dep.controller
            (c,) = dep.plan.cut
            ctrl._kept = {(c.src, c.dst): [(2, SKIP), (3, SKIP)]}
            ctrl._stalled = {dep.plan.assignment[c.dst]: 2}
            out = dep.run(instances=8)  # auto-recovers, then runs fresh
            assert out["collect"] == self.EXPECT8
            assert dep.events[-1].requeued == {}
            assert dep.events[-1].discarded >= 2

    def test_kill_host_refused_for_thread_hosts(self):
        with ClusterDeployment(_farm(), hosts=2,
                               microbatch_size=2) as dep:
            dep.run(instances=8)
            with pytest.raises(NetworkError, match="process transports"):
                dep.kill_host(0)

    def test_recover_without_failure_refused(self):
        with ClusterDeployment(_farm(), hosts=2, microbatch_size=2) as dep:
            dep.run(instances=8)
            with pytest.raises(NetworkError, match="nothing to recover"):
                dep.recover()

    def test_pipe_kill_host_restarts_warm(self):
        """The CI elastic-smoke scenario, in-suite: SIGKILL one real host
        process mid-deployment; the survivor stalls resumably, recover()
        respawns the corpse against the warm transport, replays the lost
        batch oracle-identically, and the survivor builds ZERO new jits."""
        net = _farm_factory(10, 3)
        seq = run_sequential(net, 10)["collect"]
        with ClusterDeployment(net, hosts=2, transport="pipe",
                               microbatch_size=2, timeout_s=120,
                               factory=(_farm_factory, (10, 3))) as dep:
            out = dep.run(instances=10)
            assert float(out["collect"]) == float(seq)
            victim = dep.plan.assignment["emit"]
            survivor = next(h for h in dep.plan.hosts() if h != victim)
            # each process host reports on its OWN queue — a SIGKILL landing
            # mid-report kills the corpse holding its queue's writer lock,
            # and a shared queue would deadlock the survivor's next report
            q_before = dict(dep.controller._result_qs)
            assert len({id(q) for q in q_before.values()}) == len(q_before)
            dep.kill_host(victim)
            with pytest.raises(ClusterError) as ei:
                dep.run(instances=10)
            assert any(not r.ok and not r.stalled for r in ei.value.reports)
            rec = dep.recover()
            assert float(rec["collect"]) == float(seq)
            assert dep.epoch == 2
            by_host = {r.host: r for r in rec.reports}
            # the unaffected host replayed entirely warm
            assert by_host[survivor].jit_builds == 0
            (ev,) = dep.events
            assert ev.dead == [victim] and ev.restarted == [victim]
            assert ev.refined is True
            # the corpse's (possibly lock-bricked) queues were replaced;
            # the survivor still reports on its warm one
            assert dep.controller._result_qs[victim] is not q_before[victim]
            assert dep.controller._result_qs[survivor] is q_before[survivor]
            # and the deployment is warm again end-to-end
            out = dep.run(instances=10)
            assert float(out["collect"]) == float(seq)
            assert sum(r.jit_builds for r in out.reports) == 0


class TestJaxMesh:
    def test_farm_bit_identical_over_mesh(self):
        net = _farm()
        seq = run_sequential(net, 10)["collect"]
        out = run_cluster(net, instances=10, hosts=2, transport="jaxmesh",
                          microbatch_size=3)
        assert float(out["collect"]) == float(seq)

    def test_ingress_constraint_folds_into_stage_jit(self):
        """The ROADMAP fold: a cut channel whose consumer is a jitted stage
        places the chunk inside that stage jit (_in_spec), not eagerly."""
        net = _pipeline()
        plan = partition(net, hosts=2)
        (c,) = [c for c in plan.cut]
        consumer_host = plan.assignment[c.dst]
        sub = plan.subnetwork(consumer_host)
        mesh = jax.sharding.Mesh(np.asarray([jax.devices()[0]]), ("host",))
        cn = build(sub, mesh=mesh)
        ex = PartitionExecutor(
            cn, plan=plan, host=consumer_host,
            endpoint=InProcess(), microbatch_size=2)
        assert net.procs[c.dst].kind is Kind.WORKER
        assert c.dst in ex._in_spec

    def test_named_fan_axis_degrades_to_submesh_replication(self):
        """A deployment-mesh fan axis (axis="data") does not exist on the
        per-host submeshes; its constraint degrades to replication instead
        of crashing the host (regression)."""
        net = DataParallelCollect(create=_mk_items(8), function=_sq,
                                  collector=_add, init=jnp.asarray(0.0),
                                  workers=2, axis="data", jit_combine=True)
        seq = run_sequential(net, 8)["collect"]
        out = run_cluster(net, instances=8, hosts=2, transport="jaxmesh",
                          microbatch_size=2)
        assert float(out["collect"]) == float(seq)

    def test_unknown_transport_rejected(self):
        with pytest.raises(NetworkError, match="unknown transport"):
            make_transport("carrier-pigeon")

    def test_mandelbrot_stage_per_device_host_fold(self):
        """The Mandelbrot farm with one stage per device (4 CPU devices, as
        the benchmark lays it over four chips): bit-identical to the fused
        run, every band folded once, and only the collect host copies its
        chunks to the host — the stage hosts' egress stays on the device."""
        code = textwrap.dedent("""
            import json, os, sys
            os.environ["XLA_FLAGS"] = \
                "--xla_force_host_platform_device_count=4"
            sys.path[:0] = [%r, %r]
            import dataclasses
            import jax
            import numpy as np
            from examples.mandelbrot import _assemble, make_net
            from repro.cluster import ClusterDeployment, partition
            from repro.cluster.transport import JaxMesh
            from repro.core import build

            bands = 8
            net = make_net(32, 16, bands, 20)
            one = _assemble(build(net).run(instances=bands)["collect"])
            plan = partition(net, assignment={"emit": 0, "ofa": 1,
                                              "group": 2, "afo": 3,
                                              "collect": 3})
            sent = {}

            class Probe(JaxMesh):
                def _send_transform(self, chan, value):
                    sent.setdefault(plan.assignment[chan[0]], set()).update(
                        "device" if isinstance(l, jax.Array) else "host"
                        for l in jax.tree_util.tree_leaves(value))
                    return super()._send_transform(chan, value)

            delivered = []
            fold = net.procs["collect"].fn

            def counted(acc, item):
                delivered.append(int(item[0]))
                return fold(acc, item)

            net.procs["collect"] = dataclasses.replace(
                net.procs["collect"], fn=counted)
            same = []
            with ClusterDeployment(net, plan=plan,
                                   transport=Probe(devices=jax.devices()[:4]),
                                   microbatch_size=2, trace=True) as dep:
                for _ in range(2):
                    out = dep.run(instances=bands)["collect"]
                    same.append(bool(np.array_equal(_assemble(out), one)))
                ev = dep.merged_trace()
            copies = [(e.host, e.args["ci"], e.args["nbytes"])
                      for e in ev if e.name == "to_host"]
            print(json.dumps({"same": same, "delivered": delivered,
                              "copies": copies, "n_hosts": plan.n_hosts,
                              "sent": {h: sorted(m)
                                       for h, m in sent.items()}}))
        """ % (_SRC, _ROOT))
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-3000:]
        got = json.loads(r.stdout.strip().splitlines()[-1])
        assert got["n_hosts"] == 4
        assert got["same"] == [True, True]
        rows = [b * 2 for b in range(8)]  # band height 16 // 8
        assert got["delivered"] == rows + rows  # each band once, in order
        # four chunks of two bands a batch, each one copy of (2,) int32
        # top rows and (2, 2, 32) int32 counts, all on the collect host
        chunk_bytes = 2 * 4 + 2 * 2 * 32 * 4
        assert sorted(map(tuple, got["copies"])) == sorted(
            [(3, ci, chunk_bytes) for ci in range(4)] * 2)
        assert got["sent"] == {"0": ["device"], "1": ["device"],
                               "2": ["device"]}


class TestCostPartitioning:
    """Tentpole: measured-cost planning — calibrate once, cut by TIME not
    by count, emit a perfectly ordinary PartitionPlan that faces the same
    §6.1.1 proof obligations (and hot-swaps through reconfigure)."""

    def _skewed_net(self):
        # four stages, uniform COUNT, skewed COST (stage0/stage1 heavy)
        return OnePipelineCollect(create=_mk_items(8),
                                  stage_ops=[_sq, _sq, _inc, _inc],
                                  collector=_add, init=jnp.asarray(0.0),
                                  jit_combine=True)

    def _skewed_profile(self, heavy=("stage0", "stage1")):
        costs = {name: ProcessCost(name=name, shape=(), dtype="float32",
                                   wall_s=1e-3 if name in heavy else 1e-6,
                                   out_bytes=8)
                 for name in ("emit", "stage0", "stage1", "stage2",
                              "stage3", "collect")}
        return CostProfile(costs=costs, bandwidths={"inprocess": 1e9})

    def test_cost_cut_differs_from_count_cut_and_refines(self):
        net = self._skewed_net()
        profile = self._skewed_profile()
        count_plan = partition(net, hosts=2)
        cost_plan = partition(net, assignment=cost_assignment(
            net, 2, profile, transport="inprocess"))
        a = count_plan.assignment
        assert a["stage0"] == a["stage1"]  # count piles the heavies up
        assert (cost_plan.assignment["stage0"]
                != cost_plan.assignment["stage1"])  # cost splits them 1/1
        for plan in (count_plan, cost_plan):
            assert check_refinement(net, plan)
        assert check_redeployment(net, count_plan, cost_plan)

    def test_cost_assignment_may_use_fewer_hosts(self):
        # transfer dwarfs compute: every cut costs ~1000s, so the cheapest
        # legal plan is all-on-one-host even when three are offered
        net = _pipeline()
        costs = {n: ProcessCost(name=n, shape=(), dtype="float32",
                                wall_s=1e-7, out_bytes=1 << 20)
                 for n in ("emit", "stage0", "stage1", "collect")}
        profile = CostProfile(costs=costs, bandwidths={"inprocess": 1e3})
        a = cost_assignment(net, 3, profile, transport="inprocess")
        assert len(set(a.values())) == 1
        assert check_refinement(net, partition(net, assignment=a))

    def test_calibrate_measures_every_stage(self):
        net = _pipeline()
        profile = calibrate(net, instances=4, microbatch_size=2,
                            transports=("inprocess",))
        for name in ("stage0", "stage1", "collect"):
            c = profile.costs[name]
            assert c.source == "measured"
            assert c.wall_s > 0
        assert profile.bandwidths.get("inprocess", 0) > 0
        # the json round-trip plans identically to the live profile
        rt = CostProfile.from_json(profile.to_json())
        assert (cost_assignment(net, 2, profile, transport="inprocess")
                == cost_assignment(net, 2, rt, transport="inprocess"))

    def test_hot_swap_to_cost_plan_via_reconfigure(self):
        net = self._skewed_net()
        n = 8
        seq = run_sequential(net, n)
        cost_plan = partition(net, assignment=cost_assignment(
            net, 2, self._skewed_profile(), transport="inprocess"))
        with ClusterDeployment(net, hosts=2, transport="inprocess",
                               microbatch_size=2) as dep:
            out = dep.run(instances=n)
            assert bool(out["collect"] == seq["collect"])
            ev = dep.reconfigure(plan=cost_plan)
            assert ev.mode == "reconfigure" and ev.refined is True
            assert dep.plan.assignment == cost_plan.assignment
            out2 = dep.run(instances=n)
            assert bool(out2["collect"] == seq["collect"])

    def test_coalesced_deployment_bit_identical(self):
        net = _farm(12, 3)
        seq = run_sequential(net, 12)
        with ClusterDeployment(net, hosts=2, transport="inprocess",
                               microbatch_size=2,
                               coalesce_bytes=1 << 14) as dep:
            for _ in range(2):
                out = dep.run(instances=12)
                assert bool(out["collect"] == seq["collect"])
