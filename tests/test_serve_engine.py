"""The redesigned serving API (PR 6): immutable Request/Response through
a ServeEngine over pluggable decode backends.

Fast-lane coverage: SlotPlan admission bookkeeping, engine token streams
bit-identical to sequential per-request generation, the ``max_new=0``
regression, admission-interleaving properties (hypothesis), the
clustered decode farm (inprocess) matching the local backend, epoch-bumped
scale-out with the §6.1.1 re-proof, the kill-during-serving simulator, and
the deprecated FarmScheduler shim's legacy contract."""

import dataclasses
import random
import warnings

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.dataflow import NetworkError
from repro.core.stream import SlotPlan
from repro.serve import (ClusterDecodeBackend, FarmScheduler,
                         LocalDecodeBackend, Request, Response, ServeEngine,
                         ToyLM, build_decode_model, make_decode_farm)

TOY = ("toy", 32, 8)


def _toy():
    return build_decode_model(TOY)


def _oracle_tokens(model, params, req, max_len=64):
    """The sequential reference: one request alone in a one-slot engine."""
    eng = ServeEngine(LocalDecodeBackend(model, params, n_slots=1,
                                         max_len=max_len))
    eng.submit(req)
    eng.run_until_drained()
    return eng.poll(req.rid).tokens


# ==========================================================================
# SlotPlan
# ==========================================================================

class TestSlotPlan:
    def test_claim_lowest_free_release_reuse(self):
        plan = SlotPlan(3)
        assert [plan.claim(r) for r in (10, 11, 12)] == [0, 1, 2]
        assert plan.n_free == 0
        assert plan.release(1) == 11
        assert plan.claim(13) == 1  # lowest free slot, immediately reused
        assert plan.owner(1) == 13
        assert plan.active() == [(0, 10), (1, 13), (2, 12)]

    def test_full_and_double_release_raise(self):
        plan = SlotPlan(1)
        plan.claim(0)
        with pytest.raises(NetworkError):
            plan.claim(1)
        plan.release(0)
        with pytest.raises(NetworkError):
            plan.release(0)
        with pytest.raises(NetworkError):
            SlotPlan(0)

    def test_events_record_joins_and_leaves(self):
        plan = SlotPlan(2)
        plan.claim(7)
        plan.tick()
        plan.claim(8)
        plan.release(0)
        assert [(e.step, e.kind, e.slot, e.rid) for e in plan.events] == [
            (0, "join", 0, 7), (1, "join", 1, 8), (1, "leave", 0, 7)]
        assert plan.mask().tolist() == [False, True]


# ==========================================================================
# Request / Response surface
# ==========================================================================

def test_request_immutable_prompt_coerced():
    req = Request(rid=0, prompt=[3, 5], max_new=2)
    assert req.prompt == (3, 5)  # lists coerced at construction
    with pytest.raises(dataclasses.FrozenInstanceError):
        req.max_new = 9


def test_poll_api_and_response_fields():
    model, params = _toy()
    eng = ServeEngine(LocalDecodeBackend(model, params, n_slots=2,
                                         max_len=64))
    rid = eng.submit(Request(rid=5, prompt=(3, 4), max_new=3))
    assert rid == 5
    assert eng.poll(5) is None  # queued, not finished
    with pytest.raises(KeyError):
        eng.poll(99)
    eng.run_until_drained()
    resp = eng.poll(5)
    assert isinstance(resp, Response)
    assert len(resp.tokens) == 3 and resp.finish_reason == "length"
    assert resp.ttft > 0 and resp.latency >= resp.ttft
    with pytest.raises(dataclasses.FrozenInstanceError):
        resp.tokens = ()


def test_duplicate_and_empty_submissions_rejected():
    model, params = _toy()
    eng = ServeEngine(LocalDecodeBackend(model, params, n_slots=2,
                                         max_len=64))
    eng.submit(Request(rid=0, prompt=(3,), max_new=1))
    with pytest.raises(ValueError, match="duplicate rid"):
        eng.submit(Request(rid=0, prompt=(4,), max_new=1))
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(Request(rid=1, prompt=(), max_new=1))
    eng.run_until_drained()
    assert [r.rid for r in eng.completed] == [0]


def test_max_new_zero_completes_without_slot():
    """Regression: a max_new=0 request used to burn a slot and a decode
    step; it must complete immediately at submit, zero tokens, no claim."""
    model, params = _toy()
    eng = ServeEngine(LocalDecodeBackend(model, params, n_slots=2,
                                         max_len=64))
    eng.submit(Request(rid=0, prompt=(5, 7), max_new=0))
    resp = eng.poll(0)
    assert resp is not None and resp.tokens == ()
    assert resp.finish_reason == "length" and resp.first_token_at is None
    assert eng.plan.n_free == 2 and eng.steps_run == 0


def test_eos_truncates_and_reports_reason():
    model, params = _toy()
    req = Request(rid=0, prompt=(5, 9), max_new=6)
    full = _oracle_tokens(model, params, req)
    assert len(full) == 6
    eos = full[2]  # stop on the third generated token
    eng = ServeEngine(LocalDecodeBackend(model, params, n_slots=1,
                                         max_len=64), eos_id=eos)
    eng.submit(Request(rid=0, prompt=(5, 9), max_new=6))
    eng.run_until_drained()
    resp = eng.poll(0)
    assert resp.finish_reason == "eos"
    assert resp.tokens == tuple(full[:full.index(eos) + 1])


def test_slot_events_audit_matches_trace():
    """The observability contract (ISSUE 7): every decoded request's
    Response carries exactly its own join + leave SlotEvents, the engine
    exposes the full audit trail, and that trail agrees with the trace
    recorder's admit/done instants — the two views of slot occupancy can
    never drift apart."""
    from repro.core.trace import CountingClock, TraceRecorder

    model, params = _toy()
    rec = TraceRecorder(host="serve", clock=CountingClock())
    eng = ServeEngine(LocalDecodeBackend(model, params, n_slots=2,
                                         max_len=32), recorder=rec)
    for i in range(3):  # 3 requests > 2 slots forces a slot hand-off
        eng.submit(Request(rid=i, prompt=(2 + i,), max_new=4))
    eng.run_until_drained()
    for i in range(3):
        r = eng.poll(i)
        assert len(r.slot_events) == 2, r.slot_events
        join, leave = r.slot_events
        assert (join.kind, leave.kind) == ("join", "leave")
        assert join.slot == leave.slot and join.step <= leave.step
        assert all(e.rid == i for e in r.slot_events)
    # the engine-level trail is the union of the per-response views
    trail = eng.slot_events
    assert sorted((e.rid, e.kind) for e in trail) == sorted(
        (i, k) for i in range(3) for k in ("join", "leave"))
    # ...and it matches the trace plane: one admit + one done per rid
    admits = {e.args["rid"] for e in rec.events() if e.name == "admit"}
    dones = {e.args["rid"] for e in rec.events() if e.name == "done"}
    assert admits == dones == {0, 1, 2}
    joined = {e.rid for e in trail if e.kind == "join"}
    assert joined == admits


# ==========================================================================
# Continuous batching ≡ sequential generation
# ==========================================================================

def test_engine_matches_sequential_oracle():
    model, params = _toy()
    reqs = [Request(rid=i, prompt=tuple(range(1, 2 + i)), max_new=3 + i % 3)
            for i in range(6)]  # 6 requests > 3 slots forces slot reuse
    expect = {r.rid: _oracle_tokens(model, params, r) for r in reqs}
    eng = ServeEngine(LocalDecodeBackend(model, params, n_slots=3,
                                         max_len=64))
    for r in reqs:
        eng.submit(r)
    done = eng.run_until_drained()
    assert sorted(r.rid for r in done) == list(range(6))
    for r in reqs:
        assert eng.poll(r.rid).tokens == expect[r.rid], f"req {r.rid}"


@settings(deadline=None, max_examples=10)
@given(n_slots=st.integers(min_value=1, max_value=3),
       seed=st.integers(min_value=0, max_value=3))
def test_admission_interleavings_each_rid_exactly_once(n_slots, seed):
    """Property: ANY interleaving of submits and steps yields every rid
    exactly once, bit-identical to the sequential oracle — the admission
    queue is a throughput transform, never a numerical one."""
    model, params = _toy()
    rng = random.Random(seed)
    reqs = [Request(rid=i,
                    prompt=tuple(rng.randrange(1, 32)
                                 for _ in range(rng.randrange(1, 5))),
                    max_new=rng.randrange(1, 5))
            for i in range(5)]
    expect = {r.rid: _oracle_tokens(model, params, r) for r in reqs}
    eng = ServeEngine(LocalDecodeBackend(model, params, n_slots=n_slots,
                                         max_len=64))
    i = 0
    while i < len(reqs) or eng.pending or eng._live:
        if i < len(reqs) and (rng.random() < 0.5
                              or not (eng.pending or eng._live)):
            eng.submit(reqs[i])
            i += 1
        else:
            eng.step()
    assert sorted(r.rid for r in eng.completed) == [r.rid for r in reqs]
    for r in reqs:
        assert eng.poll(r.rid).tokens == expect[r.rid]


# ==========================================================================
# The clustered decode farm
# ==========================================================================

def test_decode_farm_redeployment_refines():
    """The farm declares its per-branch relay buffering, so every replan
    passes check_redeployment (§6.1.1) — the proof reconfigure re-runs."""
    from repro.cluster.partition import check_redeployment, partition

    net = make_decode_farm(TOY, 4, 2, 32, 4)
    plans = {h: partition(net, hosts=h) for h in (1, 2, 3)}
    for a, b in ((1, 2), (2, 3), (3, 2)):
        assert check_redeployment(net, plans[a], plans[b]), f"{a}->{b}"


def test_cluster_backend_matches_local_and_scales():
    """The farm-parked backend is bit-identical to the local one, across
    an epoch-bumped scale-out mid-serving (reconfigure, not restart)."""
    model, params = _toy()
    reqs = [Request(rid=i, prompt=tuple(range(1, 2 + i)), max_new=2 + i % 2)
            for i in range(4)]
    expect = {r.rid: _oracle_tokens(model, params, r) for r in reqs}
    be = ClusterDecodeBackend(TOY, n_slots=4, shards=2, hosts=2,
                              transport="inprocess", max_len=64)
    try:
        eng = ServeEngine(be)
        for r in reqs[:2]:
            eng.submit(r)
        eng.step()
        ev = be.scale(3)  # grow the decode farm while requests are live
        assert ev.mode == "reconfigure"
        assert ev.refined is True  # §6.1.1 re-proved for the new plan
        assert be.dep.epoch == 2
        for r in reqs[2:]:
            eng.submit(r)
        eng.run_until_drained()
        for r in reqs:
            assert eng.poll(r.rid).tokens == expect[r.rid], f"req {r.rid}"
    finally:
        be.close()


def test_reconfigure_validates_arguments():
    be = ClusterDecodeBackend(TOY, n_slots=2, shards=1, hosts=1,
                              transport="inprocess", max_len=32)
    try:
        with pytest.raises(NetworkError, match="exactly one"):
            be.dep.reconfigure()
    finally:
        be.close()
    with pytest.raises(NetworkError, match="not divisible"):
        ClusterDecodeBackend(TOY, n_slots=3, shards=2, hosts=1)


@pytest.mark.parametrize("seed", [1, 7])
def test_serve_kill_scenario_green(seed):
    """Seeded host kills under a live engine: every accepted request
    answered exactly once, bit-identical (seed 7 is the regression that
    found the stale same-epoch leftovers after a completed replay)."""
    from repro.cluster.sim import run_serve_kill_scenario

    r = run_serve_kill_scenario(seed)
    assert r.ok, r.describe()
    assert r.fired >= 1  # the schedule actually injected its fault


def test_engine_adopt_exactly_once_across_crash():
    """Durable serving: the engine persists its in-flight request table, a
    crash mid-serving loses the backend AND engine, and a fresh pair
    adopts the store — every request answered exactly once, bit-identical
    to the per-request oracle (no drop, no duplicate)."""
    import shutil
    import tempfile

    from repro.cluster.durable import DeploymentStore

    model, params = _toy()
    reqs = [Request(rid=i, prompt=(3 + i, 7, 11 + i)[:1 + i % 3],
                    max_new=3 + i % 4) for i in range(6)]
    expect = {r.rid: _oracle_tokens(model, params, r, max_len=32)
              for r in reqs}
    d = tempfile.mkdtemp()
    try:
        be = ClusterDecodeBackend(TOY, n_slots=4, shards=2, hosts=2,
                                  max_len=32, prefill_chunk=4,
                                  snapshot_every=2, snapshot_dir=d)
        eng = ServeEngine(be, store=be.store)
        for r in reqs[:4]:
            eng.submit(r)
        for _ in range(4):
            eng.step()  # some requests complete, some stay in flight
        be.close()  # the crash: engine and backend both die here

        be2 = ClusterDecodeBackend(TOY, n_slots=4, shards=2, hosts=2,
                                   max_len=32, prefill_chunk=4,
                                   snapshot_every=2, snapshot_dir=d)
        try:
            eng2 = ServeEngine.adopt(be2, DeploymentStore(d))
            for r in reqs[4:]:
                eng2.submit(r)
            eng2.run_until_drained()
            answered = [resp.rid for resp in eng2.completed]
            for r in reqs:
                assert answered.count(r.rid) == 1, \
                    f"rid {r.rid} answered {answered.count(r.rid)} times"
                assert eng2.poll(r.rid).tokens == expect[r.rid], f"req {r.rid}"
        finally:
            be2.close()
    finally:
        shutil.rmtree(d, ignore_errors=True)


# ==========================================================================
# The deprecated FarmScheduler shim
# ==========================================================================

class _LegacyRequest:
    """What PR 1 callers submit: a mutable object with rid/prompt/max_new,
    expecting ``generated`` to be written onto it."""

    def __init__(self, rid, prompt, max_new):
        self.rid = rid
        self.prompt = prompt
        self.max_new = max_new


def test_shim_warns_and_fills_generated():
    model, params = _toy()
    with pytest.warns(DeprecationWarning, match="FarmScheduler"):
        sched = FarmScheduler(model, params, n_slots=2, max_len=64)
    reqs = [_LegacyRequest(i, [3 + i, 5], 2 + i % 2) for i in range(4)]
    for r in reqs:
        sched.submit(r)
    done = sched.run()
    assert done == reqs  # the very objects submitted, completion-ordered
    for r in reqs:
        want = _oracle_tokens(model, params,
                              Request(rid=100 + r.rid,
                                      prompt=tuple(r.prompt),
                                      max_new=r.max_new))
        assert r.generated == list(want)


def test_shim_legacy_views_track_engine_state():
    model, params = _toy()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        sched = FarmScheduler(model, params, n_slots=2, max_len=64)
    a = _LegacyRequest(0, [3], 5)
    b = _LegacyRequest(1, [4], 1)
    c = _LegacyRequest(2, [5], 3)
    for r in (a, b, c):
        sched.submit(r)
    assert sched.queue == [a, b, c]  # admission happens between chunks
    assert sched.slot_req == [None, None]
    n = sched.step()  # seats a+b, decodes both; b finishes (max_new=1)
    assert n == 2
    assert sched.queue == [c] and sched.slot_req == [a, None]
    assert sched.done == [b] and b.generated is not None
    sched.step()  # c takes b's freed slot
    assert sched.slot_req == [a, c]
    sched.run()
    assert len(sched.done) == 3 and sched.steps_run >= 5


def test_shim_max_new_zero_regression():
    """PR 1 burned a slot and a decode step on max_new=0; the shim (via
    the engine) completes it immediately with zero tokens."""
    model, params = _toy()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        sched = FarmScheduler(model, params, n_slots=1, max_len=64)
    r = _LegacyRequest(0, [7], 0)
    sched.submit(r)
    assert sched.done == [r] and r.generated == []
    assert sched.steps_run == 0 and sched.slot_req == [None]
