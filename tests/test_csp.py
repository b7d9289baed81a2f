"""FDR4-lite model checking (paper §4.6, §6.1.1, CSPm Definitions 1–7)."""

from hypothesis import given, settings, strategies as st

from repro.core import (DataParallelCollect, GroupOfPipelineCollects,
                        Network, OnePipelineCollect,
                        TaskParallelOfGroupCollects)
from repro.core import csp


def _f(x):
    return x


def _coll(a, x):
    return a


def _farm(workers):
    return DataParallelCollect(create=lambda i: i, function=_f,
                               collector=_coll, workers=workers,
                               explicit=True)


def test_farm_assertions_cspm_def6():
    """deadlock free / divergence free / deterministic / terminating."""
    r = csp.check(_farm(2), instances=4)
    assert r.deadlock_free
    assert r.divergence_free
    assert r.deterministic
    assert r.all_paths_terminate
    # the single outcome is the multiset {f(i)} for all i
    (outcome,) = r.outcomes       # one terminal outcome...
    (multiset,) = outcome         # ...with one Collect
    assert sorted(multiset) == sorted(("f", ("i", k)) for k in range(4))


def test_pipeline_assertions():
    net = OnePipelineCollect(create=lambda i: i, stage_ops=[_f, _f, _f],
                             collector=_coll)
    r = csp.check(net, instances=3)
    assert r.deadlock_free and r.deterministic and r.all_paths_terminate
    # value composition visible: s2(s1(s0(i)))
    (outcome,) = r.outcomes
    (multiset,) = outcome
    assert ("s2", ("s1", ("s0", ("i", 0)))) in multiset


def test_gop_equals_pog_refinement():
    """Paper CSPm Definition 7: the two composites refine each other."""
    ops = [_f, _f, _f]
    gop = GroupOfPipelineCollects(create=lambda i: i, stage_ops=ops,
                                  collector=_coll, groups=2, explicit=True)
    pog = TaskParallelOfGroupCollects(create=lambda i: i, stage_ops=ops,
                                      collector=_coll, workers=2,
                                      explicit=True)
    assert csp.trace_equivalent(gop, pog, instances=3)


def test_gop_pog_raw_trace_asymmetry():
    """Pin WHY FDR must hide the data channels (see csp.trace_equivalent
    docstring): raw collect-arrival orderings differ between topologies."""
    ops = [_f, _f, _f]
    gop = GroupOfPipelineCollects(create=lambda i: i, stage_ops=ops,
                                  collector=_coll, groups=2, explicit=True)
    pog = TaskParallelOfGroupCollects(create=lambda i: i, stage_ops=ops,
                                      collector=_coll, workers=2,
                                      explicit=True)
    ra = csp.check(gop, 3, collect_traces=True)
    rb = csp.check(pog, 3, collect_traces=True)
    assert ra.traces != rb.traces  # orderings differ ...
    assert ra.outcomes == rb.outcomes  # ... but the result never does


def test_trace_refines_is_containment():
    """``trace_refines`` is FDR's actual ``[T=`` on observable trace sets:
    reflexive, and a farm with MORE workers still refines a single-lane
    one (any interleaving it adds is already possible... it is not — the
    single lane is the stricter spec, so containment must FAIL that way
    while outcome-equivalence holds)."""
    farm1, farm2 = _farm(1), _farm(2)
    assert csp.trace_refines(farm1, farm1, instances=3)
    assert csp.trace_refines(farm2, farm2, instances=3)
    # a 1-worker farm emits arrivals in item order only; the 2-worker farm
    # may reorder — so farm2's traces contain farm1's, not vice versa
    assert csp.trace_refines(farm2, farm1, instances=3)
    assert not csp.trace_refines(farm1, farm2, instances=3)
    # ...even though the collected OUTCOME is identical (Def 7's point)
    assert csp.trace_equivalent(farm1, farm2, instances=3)


def test_trace_refines_across_relay_models():
    """The re-deployment obligation: inserting transparent relays (the
    partitioned model's transports) changes no observable trace, in either
    direction — the license to swap plan epochs under a live network."""
    from repro.cluster import abstract_partitioned_model, partition
    net = OnePipelineCollect(create=lambda i: i, stage_ops=[_f, _f],
                             collector=_coll)
    plan = partition(net, hosts=2)
    model = abstract_partitioned_model(net, plan)
    assert csp.trace_refines(net, model, instances=3)
    assert csp.trace_refines(model, net, instances=3)


def test_deadlock_detected_in_broken_model():
    """A worker ring with no source deadlocks immediately — the checker
    sees it (negative control; verify would refuse this network)."""
    from repro.core import Worker
    net = Network("broken")
    net.procs["w1"] = Worker(_f, name="w1")
    net.procs["w2"] = Worker(_f, name="w2")
    net.connect("w1", "w2")
    net.connect("w2", "w1")
    r = csp.check(net, instances=2)
    assert not r.deadlock_free


@settings(max_examples=10, deadline=None)
@given(workers=st.integers(1, 3), instances=st.integers(1, 4))
def test_farm_properties_hold_for_all_sizes(workers, instances):
    r = csp.check(_farm(workers), instances=instances)
    assert r.deadlock_free and r.deterministic and r.all_paths_terminate
    (outcome,) = r.outcomes
    assert len(outcome[0]) == instances


@settings(max_examples=6, deadline=None)
@given(groups=st.integers(1, 2), stages=st.integers(2, 3),
       instances=st.integers(1, 3))
def test_gop_pog_equivalence_for_all_sizes(groups, stages, instances):
    ops = [_f] * stages
    gop = GroupOfPipelineCollects(create=lambda i: i, stage_ops=ops,
                                  collector=_coll, groups=groups,
                                  explicit=True)
    pog = TaskParallelOfGroupCollects(create=lambda i: i, stage_ops=ops,
                                      workers=groups, collector=_coll,
                                      explicit=True)
    assert csp.trace_equivalent(gop, pog, instances=instances)
