"""Streaming microbatch executor: equivalence with the sequential oracle,
uneven chunking, backpressure from channel capacity, work-stealing schedule,
and the CSP refinement of the streaming schedule (paper §6.1.1 on ourselves)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Collect, DataParallelCollect, Emit,
                        GroupOfPipelineCollects, Network, NetworkError,
                        OnePipelineCollect, TaskParallelOfGroupCollects,
                        Worker, build, csp, run_sequential)
from repro.core.stream import (StreamExecutor, fused_chains, microbatch_plan,
                               plan_depth_lanes, slice_microbatch,
                               stack_microbatches, streaming_abstract_model,
                               synchronous_abstract_model)


def _sq(x):
    return x * x


def _inc(x):
    return x + 1.0


def _add(a, x):
    return a + x


def _mk_items(n):
    return lambda i: jnp.asarray(float(i))


class TestMicrobatchPlan:
    def test_exact_cover(self):
        assert microbatch_plan(10, 4) == [(0, 4), (4, 8), (8, 10)]
        assert microbatch_plan(8, 4) == [(0, 4), (4, 8)]
        assert microbatch_plan(3, 8) == [(0, 3)]
        assert microbatch_plan(0, 4) == []

    def test_invalid(self):
        with pytest.raises(NetworkError):
            microbatch_plan(8, 0)
        with pytest.raises(NetworkError):
            microbatch_plan(-1, 4)

    def test_slice_roundtrip(self):
        x = {"a": jnp.arange(10.0), "b": jnp.arange(20.0).reshape(10, 2)}
        chunks = [slice_microbatch(x, lo, hi)
                  for lo, hi in microbatch_plan(10, 3)]
        back = jax.tree_util.tree_map(
            lambda *ls: jnp.concatenate(ls), *chunks)
        np.testing.assert_array_equal(np.asarray(back["a"]),
                                      np.asarray(x["a"]))
        np.testing.assert_array_equal(np.asarray(back["b"]),
                                      np.asarray(x["b"]))

    def test_stack_microbatches(self):
        x = jnp.arange(12.0).reshape(12, 1)
        mb = stack_microbatches(x, 3)
        assert mb.shape == (3, 4, 1)
        with pytest.raises(NetworkError, match="not divisible"):
            stack_microbatches(x, 5)


class TestStreamingEquivalence:
    """run_streaming ≡ run_sequential ≡ run, bit-identical."""

    @pytest.mark.parametrize("mb", [1, 3, 4, 8, 16])
    def test_farm(self, mb):
        net = DataParallelCollect(create=_mk_items(10), function=_sq,
                                  collector=_add, init=jnp.asarray(0.0),
                                  workers=3, jit_combine=True)
        cn = build(net)
        seq = run_sequential(net, 10)["collect"]
        fused = cn.run(instances=10)["collect"]
        strm = cn.run_streaming(instances=10, microbatch_size=mb)["collect"]
        assert float(seq) == float(fused) == float(strm)
        assert cn.stream_stats.n_chunks == len(microbatch_plan(10, mb))

    @pytest.mark.parametrize("mb", [2, 3, 7])
    def test_pipeline_uneven_chunks(self, mb):
        """Microbatch sizes that do not divide the item count."""
        net = OnePipelineCollect(create=_mk_items(7), stage_ops=[_sq, _inc],
                                 collector=_add, init=jnp.asarray(0.0),
                                 jit_combine=True)
        cn = build(net)
        seq = run_sequential(net, 7)["collect"]
        strm = cn.run_streaming(instances=7, microbatch_size=mb)["collect"]
        assert float(seq) == float(strm)

    @pytest.mark.parametrize("pattern", ["gop", "pog"])
    def test_composites(self, pattern):
        kw = dict(create=_mk_items(12), stage_ops=[_sq, _inc, _inc],
                  collector=_add, init=jnp.asarray(0.0), jit_combine=True)
        if pattern == "gop":
            net = GroupOfPipelineCollects(groups=3, **kw)
        else:
            net = TaskParallelOfGroupCollects(workers=3, **kw)
        cn = build(net)
        seq = run_sequential(net, 12)["collect"]
        strm = cn.run_streaming(instances=12, microbatch_size=5)["collect"]
        assert float(seq) == float(strm)

    def test_explicit_farm_work_stealing(self):
        """Explicit per-worker branches: whole chunks route to one lane and
        the result is still the oracle's."""
        net = DataParallelCollect(create=_mk_items(9), function=_sq,
                                  collector=_add, init=jnp.asarray(0.0),
                                  workers=3, jit_combine=True, explicit=True)
        cn = build(net)
        seq = run_sequential(net, 9)["collect"]
        strm = cn.run_streaming(instances=9, microbatch_size=2)["collect"]
        assert float(seq) == float(strm)
        sched = cn.stream_stats.schedule
        assert len(sched) == 5  # one lane assignment per chunk
        assert {lane for _, lane in sched} <= {0, 1, 2}

    def test_explicit_gop_ragged_chunks(self):
        """Explicit OneFanList with homogeneous branches streams whole chunks
        round-robin — any microbatch size works, even non-divisible ones."""
        net = GroupOfPipelineCollects(
            create=_mk_items(12), stage_ops=[_sq, _inc], collector=_add,
            init=jnp.asarray(0.0), jit_combine=True, groups=3, explicit=True)
        cn = build(net)
        fused = cn.run(instances=12)["collect"]
        strm = cn.run_streaming(instances=12, microbatch_size=5)["collect"]
        assert float(fused) == float(strm)
        assert float(strm) == sum(i * i + 1 for i in range(12))

    def test_combine_reducer_bit_identical(self):
        """COMBINE folds carry across chunks: same float association as the
        whole-batch fold (random float32s make reassociation visible).
        Streaming ≡ logged bitwise; fused may differ only by XLA's own
        whole-program reassociation, so it gets an approx check."""
        from repro.core import CombineNto1, OneSeqCastList
        rng = np.random.default_rng(7)
        vals = jnp.asarray(rng.normal(size=32) * 100.0, jnp.float32)
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
        cn = build(net)
        fused = cn.run(instances=32)["collect"]
        logged = cn.run(instances=32, logged=True)["collect"]
        strm = cn.run_streaming(instances=32, microbatch_size=5)["collect"]
        assert np.asarray(logged).tobytes() == np.asarray(strm).tobytes()
        np.testing.assert_allclose(np.asarray(fused), np.asarray(strm),
                                   rtol=1e-6)

    def test_heterogeneous_fan_ragged_chunks_fail_fast(self):
        """Branches with distinct tags can't take whole chunks; an indivisible
        microbatch is refused up front, naming microbatch_size."""
        from repro.core import ListSeqOne, OneFanList
        net = Network("hetero")
        net.add(Emit(_mk_items(12), name="emit"),
                OneFanList(name="ofl"))
        for w, fn in enumerate([_sq, _inc, lambda x: x * 3.0]):
            net.procs[f"w{w}"] = Worker(fn, name=f"w{w}", tag=f"f{w}")
            net.connect("ofl", f"w{w}")
        net.procs["lso"] = ListSeqOne(name="lso")
        for w in range(3):
            net.connect(f"w{w}", "lso")
        net._tail = "lso"
        net.add(Collect(_add, init=jnp.asarray(0.0), jit_combine=True,
                        name="collect"))
        cn = build(net)
        with pytest.raises(NetworkError, match="microbatch_size=5"):
            cn.run_streaming(instances=12, microbatch_size=5)
        # divisible microbatch streams fine and matches the oracle
        seq = run_sequential(net, 12)["collect"]
        strm = cn.run_streaming(instances=12, microbatch_size=6)["collect"]
        assert float(seq) == float(strm)

    def test_deep_heterogeneous_fan_uses_item_round_robin(self):
        """Branches whose FIRST stages share a tag but whose deeper stages
        differ are heterogeneous: chunks split at item level, matching the
        sequential oracle."""
        from repro.core import ListSeqOne, OneFanList
        net = Network("deep-hetero")
        net.add(Emit(_mk_items(8), name="emit"), OneFanList(name="ofl"))
        chains = [[("a", lambda x: x), ("b0", lambda x: x + 1.0)],
                  [("a", lambda x: x), ("b1", lambda x: x * 100.0)]]
        net.procs["lso"] = ListSeqOne(name="lso")
        for b, chain in enumerate(chains):
            prev = "ofl"
            for s, (tag, fn) in enumerate(chain):
                wn = f"b{b}s{s}"
                net.procs[wn] = Worker(fn, name=wn, tag=tag)
                net.connect(prev, wn)
                prev = wn
            net.connect(prev, "lso")
        net._tail = "lso"
        net.add(Collect(_add, init=jnp.asarray(0.0), jit_combine=True,
                        name="collect"))
        cn = build(net)
        seq = run_sequential(net, 8)["collect"]
        strm = cn.run_streaming(instances=8, microbatch_size=2)["collect"]
        assert float(seq) == float(strm)

    def test_invalid_lanes_rejected(self):
        net = DataParallelCollect(create=_mk_items(4), function=_sq,
                                  collector=_add, init=jnp.asarray(0.0),
                                  workers=2, jit_combine=True)
        cn = build(net)
        for lanes in (0, -1):
            with pytest.raises(NetworkError, match="lanes"):
                cn.run_streaming(instances=4, microbatch_size=2, lanes=lanes)

    def test_dict_pytree_items(self):
        """Items that ARE dict pytrees stream whole to every stage (a plain
        dict batch must never be mistaken for the cluster's per-Emit
        EmitChunks map — regression)."""
        net = DataParallelCollect(
            create=lambda i: {"a": jnp.asarray(float(i)),
                              "emit": jnp.asarray(float(2 * i))},
            function=lambda d: {"a": d["a"] * d["emit"],
                                "emit": d["emit"]},
            collector=lambda acc, d: acc + d["a"],
            init=jnp.asarray(0.0), workers=2, jit_combine=True)
        cn = build(net)
        seq = run_sequential(net, 6)["collect"]
        strm = cn.run_streaming(instances=6, microbatch_size=2)["collect"]
        assert float(seq) == float(strm) == float(sum(2.0 * i * i
                                                      for i in range(6)))

    def test_host_side_collector(self):
        net = DataParallelCollect(
            create=_mk_items(5), function=_sq,
            collector=lambda acc, x: {**acc, len(acc): float(x)},
            init={}, workers=2, jit_combine=False)
        out = build(net).run_streaming(instances=5, microbatch_size=2)
        assert out["collect"] == {i: float(i * i) for i in range(5)}

    def test_finalise_applies(self):
        net = DataParallelCollect(create=_mk_items(6), function=_sq,
                                  collector=_add, init=jnp.asarray(0.0),
                                  finalise=lambda acc: acc * 10.0,
                                  workers=2, jit_combine=True)
        cn = build(net)
        assert float(cn.run_streaming(instances=6, microbatch_size=4)
                     ["collect"]) == 10.0 * sum(i * i for i in range(6))

    def test_executor_reuse_is_cached(self):
        net = OnePipelineCollect(create=_mk_items(6), stage_ops=[_sq, _inc],
                                 collector=_add, init=jnp.asarray(0.0),
                                 jit_combine=True)
        cn = build(net)
        a = cn.run_streaming(instances=6, microbatch_size=2)["collect"]
        b = cn.run_streaming(instances=6, microbatch_size=2)["collect"]
        assert float(a) == float(b)
        assert len(cn._streams) == 1  # same executor (and stage jits) reused


def _band_net(seen: list, band_h: int = 3, width: int = 5):
    """A farm shaped like the Mandelbrot example: each item is a band's top
    row and its int32 counts; the host fold keeps bands in a dict."""
    def create(i):
        return jnp.asarray(i * band_h, jnp.int32)

    def render(row0):
        rows = row0 + jnp.arange(band_h, dtype=jnp.int32)[:, None]
        return (row0, rows * 7 + jnp.arange(width, dtype=jnp.int32))

    def collector(acc, item):
        seen.append(item)
        row0, cnt = item
        acc[int(row0)] = np.asarray(cnt)
        return acc

    return DataParallelCollect(create=create, function=render,
                               collector=collector, init={}, workers=2)


def _float_net(seen: list):
    def collector(acc, x):
        seen.append(x)
        return {**acc, len(acc): float(x)}

    return DataParallelCollect(create=_mk_items(7), function=_sq,
                               collector=collector, init={}, workers=2)


def _run_mode(net, mode: str, n: int):
    if mode == "sequential":
        return run_sequential(net, n)["collect"]
    cn = build(net)
    if mode == "streaming":  # microbatches of 2: the last chunk holds one
        return cn.run_streaming(instances=n, microbatch_size=2)["collect"]
    return cn.run(instances=n, logged=mode == "logged")["collect"]


def _device_items(net, n: int) -> list:
    """The items as per-item slices of the device stream."""
    cn = build(net)
    stream = jax.vmap(net.procs["group"].fn)(cn.make_batch(n))
    return [jax.tree_util.tree_map(lambda a: np.asarray(a[i]), stream)
            for i in range(n)]


class TestHostFoldItems:
    """A host Collect (``jit_combine=False``) is handed host (numpy) items:
    each chunk comes to the host in one copy, and the fold slices it there."""

    @pytest.mark.parametrize("make", [_band_net, _float_net],
                             ids=["bands", "floats"])
    @pytest.mark.parametrize("mode", ["run", "logged", "streaming"])
    def test_items_are_numpy_equal_to_device_slices(self, make, mode):
        seen: list = []
        _run_mode(make(seen), mode, 7)
        want = _device_items(make([]), 7)
        assert len(seen) == 7
        for got, ref in zip(seen, want):
            got_leaves = jax.tree_util.tree_leaves(got)
            ref_leaves = jax.tree_util.tree_leaves(ref)
            assert len(got_leaves) == len(ref_leaves)
            for g, r in zip(got_leaves, ref_leaves):
                assert isinstance(g, (np.ndarray, np.generic)), type(g)
                assert not isinstance(g, jax.Array)
                assert g.dtype == r.dtype and np.shape(g) == r.shape
                np.testing.assert_array_equal(g, r)

    @pytest.mark.parametrize("make", [_band_net, _float_net],
                             ids=["bands", "floats"])
    def test_results_bit_identical_across_modes(self, make):
        outs = {mode: _run_mode(make([]), mode, 7)
                for mode in ("sequential", "run", "logged", "streaming")}
        seq = outs.pop("sequential")
        assert len(seq) == 7
        for mode, out in outs.items():
            assert out.keys() == seq.keys(), mode
            for k in seq:
                a, b = np.asarray(seq[k]), np.asarray(out[k])
                assert a.dtype == b.dtype, mode
                np.testing.assert_array_equal(a, b)

    def test_device_fold_still_sees_device_values(self):
        """``jit_combine=True`` folds stay on the device: no host copy."""
        net = DataParallelCollect(create=_mk_items(7), function=_sq,
                                  collector=_add, init=jnp.asarray(0.0),
                                  workers=2, jit_combine=True)
        cn = build(net)
        for out in (cn.run(instances=7)["collect"],
                    cn.run_streaming(instances=7,
                                     microbatch_size=2)["collect"]):
            assert isinstance(out, jax.Array)
            assert float(out) == float(sum(i * i for i in range(7)))


class TestChunkReplay:
    """Chunk-replay bookkeeping (the cluster control plane's foundation):
    an interrupted run captures a resumable state iff the interruption hit
    before the chunk had any effect, and resuming replays only the tail."""

    class _PeerDied(NetworkError):
        pass

    def _interruptible(self, fail_at: int):
        net = OnePipelineCollect(create=_mk_items(8), stage_ops=[_sq, _inc],
                                 collector=_add, init=jnp.asarray(0.0),
                                 jit_combine=True)
        cn = build(net)
        ex = StreamExecutor(cn, microbatch_size=2)
        ex._resumable_errors = (self._PeerDied,)
        orig = ex._chunk_inputs
        trips = {"armed": True}

        def flaky(ci, lo, hi, batch):
            if ci == fail_at and trips["armed"]:
                trips["armed"] = False
                raise self._PeerDied(f"peer died before chunk {ci}")
            return orig(ci, lo, hi, batch)

        ex._chunk_inputs = flaky
        return net, ex

    def test_resume_replays_only_the_tail(self):
        net, ex = self._interruptible(fail_at=2)
        batch = jnp.arange(8, dtype=jnp.float32)
        with pytest.raises(self._PeerDied):
            ex.run(batch)
        st = ex.replay_state
        assert st is not None and st.next_ci == 2
        out = ex.resume_plan(batch)
        assert float(out["collect"]) == float(
            run_sequential(net, 8)["collect"])
        assert ex.stats.replays == 1 and ex.stats.resumed_at == 2
        assert "replays=1@chunk2" in ex.stats.summary()
        assert ex.replay_state is None  # consumed by the resume

    def test_non_resumable_error_leaves_no_state(self):
        net, ex = self._interruptible(fail_at=1)
        ex._resumable_errors = ()  # the same failure, now non-resumable
        with pytest.raises(self._PeerDied):
            ex.run(jnp.arange(8, dtype=jnp.float32))
        assert ex.replay_state is None
        with pytest.raises(NetworkError, match="no interrupted run"):
            ex.resume_plan(None)

    def test_start_ci_runs_an_aligned_tail(self):
        """run with start_ci=k streams chunks k.. with chunk ids aligned to
        the full plan (what a restarted cluster host replays)."""
        net = OnePipelineCollect(create=_mk_items(8), stage_ops=[_sq, _inc],
                                 collector=_add, init=jnp.asarray(0.0),
                                 jit_combine=True)
        ex = StreamExecutor(build(net), microbatch_size=2)
        batch = jnp.arange(8, dtype=jnp.float32)
        plan = microbatch_plan(8, 2)
        tail = ex._run_plan(plan, batch, start_ci=2)
        # only items 4..7 flowed: the fold covers the tail alone
        assert float(tail["collect"]) == float(sum(i * i + 1
                                                   for i in range(4, 8)))


class TestBackpressure:
    def test_depth_from_channel_capacity(self):
        """A buffered channel's capacity bounds the in-flight chunk count."""
        net = Network("capped")
        net.add(Emit(_mk_items(8), name="emit"))
        net.add(Worker(_sq, name="w"))
        net.procs["collect"] = Collect(_add, init=jnp.asarray(0.0),
                                       jit_combine=True, name="collect")
        net.connect("w", "collect", capacity=1)
        assert net.min_capacity() == 1
        cn = build(net)
        seq = run_sequential(net, 8)["collect"]
        strm = cn.run_streaming(instances=8, microbatch_size=2)["collect"]
        assert float(seq) == float(strm)
        assert cn.stream_stats.depth == 1
        assert cn.stream_stats.stalls == 3  # 4 chunks through a depth-1 pipe

    def test_depth_bounds_unretired_chunks(self):
        """Backpressure retires BEFORE dispatch: never more than `depth`
        chunks un-retired (capacity-k channel semantics, not k+1)."""
        from repro.core.stream import StreamExecutor
        net = OnePipelineCollect(create=_mk_items(8), stage_ops=[_sq, _inc],
                                 collector=_add, init=jnp.asarray(0.0),
                                 jit_combine=True)
        cn = build(net)
        ex = StreamExecutor(cn, microbatch_size=2, max_in_flight=1)
        seen = []
        orig = ex._dispatch_chunk

        def spy(ci, chunk, final):
            seen.append(ci)
            return orig(ci, chunk, final)

        ex._dispatch_chunk = spy
        orig_retire = ex._retire
        retired = []
        ex._retire = lambda e, h: (retired.append(e[0]), orig_retire(e, h))[1]
        ex.run(cn.make_batch(8))
        # chunk ci is only dispatched after chunk ci-1 retired (depth 1)
        for ci in seen[1:]:
            assert ci - 1 in retired[:ci]

    def test_default_depth_and_override(self):
        net = OnePipelineCollect(create=_mk_items(8), stage_ops=[_sq, _inc],
                                 collector=_add, init=jnp.asarray(0.0),
                                 jit_combine=True)
        cn = build(net)
        cn.run_streaming(instances=8, microbatch_size=2)
        assert cn.stream_stats.depth == 2  # rendezvous channels → default
        cn.run_streaming(instances=8, microbatch_size=2, max_in_flight=4)
        assert cn.stream_stats.depth == 4
        assert cn.stream_stats.stalls == 0  # 4 chunks fit entirely in flight


class TestRefinement:
    """The streaming schedule trace-refines the synchronous one (the paper's
    ``[T=`` check, §6.1.1, applied to our own runtime)."""

    @pytest.mark.parametrize("lanes", [1, 2, 3])
    def test_pipeline_schedule_refines(self, lanes):
        net = OnePipelineCollect(create=_mk_items(4), stage_ops=[_sq, _inc],
                                 collector=_add, init=jnp.asarray(0.0),
                                 jit_combine=True)
        sync = synchronous_abstract_model(net)
        strm = streaming_abstract_model(net, lanes=lanes)
        assert csp.trace_equivalent(strm, sync, instances=3)

    def test_farm_schedule_refines(self):
        net = DataParallelCollect(create=_mk_items(4), function=_sq,
                                  collector=_add, init=jnp.asarray(0.0),
                                  workers=3, jit_combine=True)
        assert csp.trace_equivalent(streaming_abstract_model(net, lanes=2),
                                    synchronous_abstract_model(net),
                                    instances=3)

    def test_streaming_model_is_safe(self):
        """Deadlock-free, divergence-free, terminating — CSPm Definition 6
        for the streaming schedule itself."""
        net = OnePipelineCollect(create=_mk_items(4), stage_ops=[_sq, _inc],
                                 collector=_add, init=jnp.asarray(0.0),
                                 jit_combine=True)
        r = csp.check(streaming_abstract_model(net, lanes=2), instances=3)
        assert r.deadlock_free and r.divergence_free
        assert r.all_paths_terminate and r.deterministic


class TestDonationTelemetry:
    """ROADMAP satellite: per-stage buffer-donation outcomes are recorded in
    stream_stats (and printed by benchmarks/stream.py)."""

    def test_stages_recorded(self):
        net = OnePipelineCollect(create=_mk_items(8), stage_ops=[_sq, _inc],
                                 collector=_add, init=jnp.asarray(0.0),
                                 jit_combine=True)
        cn = build(net)
        cn.run_streaming(instances=8, microbatch_size=2)
        stats = cn.stream_stats
        # the two pipeline stages fuse into one chain; telemetry records the
        # fused unit (unfused mode still records per stage, below)
        assert set(stats.donation) == {"stage0+stage1"}
        cn.run_streaming(instances=8, microbatch_size=2, fuse=False)
        assert set(cn.stream_stats.donation) == {"stage0", "stage1"}
        stats = cn.stream_stats
        for req, hon in stats.donation.values():
            assert req >= hon >= 0
        if jax.default_backend() == "cpu":
            # CPU: the executor never requests donation — telemetry says so
            assert not stats.donation_enabled
            assert all(req == 0 for req, _ in stats.donation.values())
            assert "disabled" in stats.donation_summary()
        else:
            assert stats.donation_enabled

    def test_summary_counts_in_stream_summary(self):
        net = OnePipelineCollect(create=_mk_items(6), stage_ops=[_sq, _inc],
                                 collector=_add, init=jnp.asarray(0.0),
                                 jit_combine=True)
        cn = build(net)
        cn.run_streaming(instances=6, microbatch_size=3)
        assert "donated=" in cn.stream_stats.summary()


class TestChainFusion:
    """Intra-partition chain fusion: maximal linear Worker/Engine runs
    compile into one per-chunk jit, results stay bit-identical, and the
    fused schedule's CSP abstraction still trace-refines the synchronous
    model (the fusion is observationally invisible)."""

    def test_chains_found_on_pipeline(self):
        net = OnePipelineCollect(create=_mk_items(8),
                                 stage_ops=[_sq, _inc, _inc],
                                 collector=_add, init=jnp.asarray(0.0),
                                 jit_combine=True)
        assert fused_chains(net) == [("stage0", "stage1", "stage2")]

    def test_no_chain_across_fan(self):
        """A fan boundary (or any connector) breaks the run."""
        net = DataParallelCollect(create=_mk_items(8), function=_sq,
                                  collector=_add, init=jnp.asarray(0.0),
                                  workers=3, jit_combine=True, explicit=True)
        assert fused_chains(net) == []  # one worker per branch: nothing linear

    def test_branch_internal_chains_fuse(self):
        """Chains INSIDE a fan branch fuse; the fan itself never does."""
        net = GroupOfPipelineCollects(
            create=_mk_items(12), stage_ops=[_sq, _inc], collector=_add,
            init=jnp.asarray(0.0), jit_combine=True, groups=3, explicit=True)
        chains = fused_chains(net)
        assert len(chains) == 3 and all(len(c) == 2 for c in chains)

    @pytest.mark.parametrize("mb", [2, 3, 7])
    def test_fused_bit_identical(self, mb):
        net = OnePipelineCollect(create=_mk_items(7),
                                 stage_ops=[_sq, _inc, lambda x: x * 3.0],
                                 collector=_add, init=jnp.asarray(0.0),
                                 jit_combine=True)
        cn = build(net)
        seq = run_sequential(net, 7)["collect"]
        fused = cn.run_streaming(instances=7, microbatch_size=mb)["collect"]
        unfused = cn.run_streaming(instances=7, microbatch_size=mb,
                                   fuse=False)["collect"]
        assert float(seq) == float(fused) == float(unfused)
        assert cn._streams[(mb, None, None, True)].stats.fused == [
            ("stage0", "stage1", "stage2")]

    def test_stats_record_fusion(self):
        net = OnePipelineCollect(create=_mk_items(8), stage_ops=[_sq, _inc],
                                 collector=_add, init=jnp.asarray(0.0),
                                 jit_combine=True)
        cn = build(net)
        cn.run_streaming(instances=8, microbatch_size=2)
        assert cn.stream_stats.fused == [("stage0", "stage1")]
        assert "fused_chains=1" in cn.stream_stats.summary()
        assert "stage0+stage1" in cn.stream_stats.fused_summary()

    def test_warm_executor_traces_once(self):
        """The compile-counter hook: re-running a warm executor with
        same-shape batches never re-traces a stage jit."""
        net = OnePipelineCollect(create=_mk_items(8), stage_ops=[_sq, _inc],
                                 collector=_add, init=jnp.asarray(0.0),
                                 jit_combine=True)
        cn = build(net)
        ex = StreamExecutor(cn, microbatch_size=2)
        built = []
        ex.on_jit_build = built.append
        ex.run(cn.make_batch(8))
        first_traces = dict(ex.trace_counts)
        first_builds = ex.jit_builds
        assert built and first_builds > 0
        for _ in range(2):
            ex.run(cn.make_batch(8))
        assert ex.jit_builds == first_builds
        assert ex.trace_counts == first_traces

    @pytest.mark.parametrize("lanes", [1, 2])
    def test_fused_schedule_refines_sync(self, lanes):
        net = OnePipelineCollect(create=_mk_items(4), stage_ops=[_sq, _inc],
                                 collector=_add, init=jnp.asarray(0.0),
                                 jit_combine=True)
        fusedm = streaming_abstract_model(net, lanes=lanes, fused=True)
        sync = synchronous_abstract_model(net)
        assert csp.trace_equivalent(fusedm, sync, instances=3)
        assert csp.trace_equivalent(sync, fusedm, instances=3)

    def test_fused_model_is_safe(self):
        net = OnePipelineCollect(create=_mk_items(4), stage_ops=[_sq, _inc],
                                 collector=_add, init=jnp.asarray(0.0),
                                 jit_combine=True)
        r = csp.check(streaming_abstract_model(net, lanes=2, fused=True),
                      instances=3)
        assert r.deadlock_free and r.divergence_free
        assert r.all_paths_terminate and r.deterministic

    def test_plan_depth_lanes_matches_executor(self):
        net = DataParallelCollect(create=_mk_items(8), function=_sq,
                                  collector=_add, init=jnp.asarray(0.0),
                                  workers=3, jit_combine=True, explicit=True)
        cn = build(net)
        ex = StreamExecutor(cn, microbatch_size=2)
        assert plan_depth_lanes(net, None, None) == (ex.depth, ex.lanes)
        assert plan_depth_lanes(net, 5, None) == (5, 5)
        assert plan_depth_lanes(net, None, 7)[1] == 7
        with pytest.raises(NetworkError, match="lanes"):
            plan_depth_lanes(net, None, 0)
        with pytest.raises(NetworkError, match="max_in_flight"):
            plan_depth_lanes(net, 0, None)


class TestMeshFoldedConstraints:
    """ROADMAP satellite: per-chunk sharding constraints are folded into the
    stage jits (with_sharding_constraint inside the per-stage program)
    instead of eager device_put between stages."""

    def test_in_spec_populated_and_results_identical(self):
        from repro.core.stream import StreamExecutor
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1,), ("data",))
        net = DataParallelCollect(create=_mk_items(8), function=_sq,
                                  collector=_add, init=jnp.asarray(0.0),
                                  workers=2, axis="data", jit_combine=True)
        cn = build(net, mesh=mesh)
        ex = StreamExecutor(cn, microbatch_size=2)
        # the farm worker's input constraint lives in its stage jit now
        assert "group" in ex._in_spec
        strm = ex.run(cn.make_batch(8))["collect"]
        seq = run_sequential(net, 8)["collect"]
        assert float(strm) == float(seq)
