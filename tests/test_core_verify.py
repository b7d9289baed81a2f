"""gppBuilder legality checking (paper §11.4): verify() accepts every
network the pattern combinators can build and refuses each illegal shape."""

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (Collect, DataParallelCollect, Emit,
                        GroupOfPipelineCollects, Network, NetworkError,
                        OnePipelineCollect, TaskParallelOfGroupCollects,
                        Worker, verify)


def _f(x):
    return x


def _coll(a, x):
    return a


def test_verify_farm_ok():
    net = DataParallelCollect(create=lambda i: i, function=_f,
                              collector=_coll, workers=4, explicit=True)
    rep = verify(net)
    assert [c[0] for c in rep.checks] == [
        "terminals", "acyclic", "reachability", "arity", "channel-specs"]


def test_no_emit_refused():
    net = Network("x")
    net.add(Worker(_f, name="w"), Collect(_coll, name="c"))
    with pytest.raises(NetworkError, match="no Emit"):
        verify(net)


def test_no_collect_refused():
    net = Network("x").add(Emit(lambda i: i, name="e"), Worker(_f, name="w"))
    # worker output dropped AND no collect
    with pytest.raises(NetworkError):
        verify(net)


def test_cycle_refused():
    net = Network("x").add(Emit(lambda i: i, name="e"),
                           Worker(_f, name="w1"), Worker(_f, name="w2"),
                           Collect(_coll, name="c"))
    net.channels.append(type(net.channels[0])("w2", "w1"))
    with pytest.raises(NetworkError, match="cycle|I/O-SEQ"):
        verify(net)


def test_orphan_refused():
    net = Network("x").add(Emit(lambda i: i, name="e"),
                           Worker(_f, name="w"), Collect(_coll, name="c"))
    net.procs["orphan"] = Worker(_f, name="orphan")
    net.connect("w", "orphan")
    with pytest.raises(NetworkError, match="cannot reach any Collect"):
        verify(net)


def test_shared_producer_refused():
    # two producers into a Worker (not a reducer) — reference sharing
    net = Network("x")
    net.add(Emit(lambda i: i, name="e1"), Worker(_f, name="w"),
            Collect(_coll, name="c"))
    net.procs["e2"] = Emit(lambda i: i, name="e2")
    net.connect("e2", "w")  # second producer into the Worker
    with pytest.raises(NetworkError, match="producers|I/O-SEQ"):
        verify(net)


@settings(max_examples=25, deadline=None)
@given(workers=st.integers(1, 6), stages=st.integers(2, 5),
       kind=st.sampled_from(["farm", "pipe", "gop", "pog"]))
def test_all_pattern_networks_verify(workers, stages, kind):
    """Property: every network the combinators build is legal (the paper's
    claim that builder-constructed networks are correct by construction)."""
    ops = [_f] * stages
    if kind == "farm":
        net = DataParallelCollect(create=lambda i: i, function=_f,
                                  collector=_coll, workers=workers,
                                  explicit=True)
    elif kind == "pipe":
        net = OnePipelineCollect(create=lambda i: i, stage_ops=ops,
                                 collector=_coll)
    elif kind == "gop":
        net = GroupOfPipelineCollects(create=lambda i: i, stage_ops=ops,
                                      collector=_coll, groups=workers,
                                      explicit=True)
    else:
        net = TaskParallelOfGroupCollects(create=lambda i: i, stage_ops=ops,
                                          collector=_coll, workers=workers,
                                          explicit=True)
    verify(net)  # must not raise


def test_pipeline_needs_two_stages():
    with pytest.raises(ValueError, match="at least two stages"):
        OnePipelineCollect(create=lambda i: i, stage_ops=[_f],
                           collector=_coll)
