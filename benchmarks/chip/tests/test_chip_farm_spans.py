"""The readers of the farm's collect-host spans (``collect_fold_ms.farm``,
``collect_ready_ms.farm``, ``idle_in_fold_share.farm``) on hand-made rows
and on a recorded excerpt (``data/farm_spans_excerpt.json``: three batches
of a traced v5e run, the worker chip's programs and every host's spans)."""

import json
import types
from pathlib import Path

import pytest

from harness import common, tracing

EXCERPT = Path(__file__).resolve().parent / "data" / "farm_spans_excerpt.json"


def _run(spans, *, window=(0.0, 10.0), batches=2, programs=None,
         collect=3):
    cfg = dict(common.config_file(common.benchmark(), "mandelbrot-farm"))
    cfg["assignment"] = dict(cfg["assignment"], collect=collect)
    trace = None if programs is None else {"devices": [
        {"name": "/device:TPU:2", "programs": [list(p) for p in programs]}]}
    return types.SimpleNamespace(
        cfg=cfg, trace_data=trace,
        data={"host_spans": spans, "trace_window": window,
              "traced_batches": batches, "worker_device": 2})


def _read(name, r):
    return common.load_module("metrics", name).read(r)


def test_spans_are_clipped_to_the_traced_stretch():
    r = _run([(3, "fold", -1.0, 0.5), (3, "fold", 4.0, 4.25),
              (3, "fold", 9.5, 12.0), (3, "fold", 11.0, 12.0),
              (3, "ready", 1.0, 2.0)])
    # 0.5 + 0.25 + 0.5 s over 2 batches
    assert _read("collect_fold_ms.farm", r) == pytest.approx(625.0)
    assert _read("collect_ready_ms.farm", r) == pytest.approx(500.0)


def test_the_collect_host_comes_from_the_assignment():
    spans = [(3, "fold", 1.0, 2.0), (1, "fold", 2.0, 5.0),
             (1, "ready", 0.0, 1.0), (3, "retire", 0.0, 9.0)]
    assert _read("collect_fold_ms.farm", _run(spans)) == pytest.approx(500.0)
    assert _read("collect_fold_ms.farm", _run(spans, collect=1)) == \
        pytest.approx(1500.0)
    assert _read("collect_ready_ms.farm", _run(spans, collect=1)) == \
        pytest.approx(500.0)
    # host 3 has no ready span
    assert _read("collect_ready_ms.farm", _run(spans)) is None


def test_idle_in_fold_is_the_overlap_of_folds_with_idle_gaps():
    # the worker chip runs [1, 3) and [5, 6): idle [0, 1), [3, 5), [6, 10)
    programs = [("jit_stage_group", 1.0, 2.0), ("jit_stage_group", 5.0, 1.0)]
    # folds cover [0.5, 2) (0.5 s of it idle), [4, 5.5) (1 s idle) and
    # overlap each other in [4.5, 5), counted once
    spans = [(3, "fold", 0.5, 2.0), (3, "fold", 4.0, 5.5),
             (3, "fold", 4.5, 5.0), (2, "fold", 6.0, 10.0)]
    r = _run(spans, programs=programs)
    assert _read("idle_in_fold_share.farm", r) == pytest.approx(
        100.0 * 1.5 / 7.0)
    # a device without programs has no idle time to put down to anything
    assert _read("idle_in_fold_share.farm", _run(spans, programs=[])) is None


@pytest.mark.parametrize("name", ["collect_fold_ms.farm",
                                  "collect_ready_ms.farm",
                                  "idle_in_fold_share.farm"])
def test_nothing_to_read_without_spans(name):
    programs = [("jit_stage_group", 1.0, 2.0)]
    assert _read(name, _run([], programs=programs)) is None
    # spans, but none of the collect host's kind inside the stretch
    outside = [(3, "fold", 11.0, 12.0), (3, "ready", -2.0, -1.0),
               (3, "retire", 0.0, 10.0)]
    assert _read(name, _run(outside, programs=programs)) is None
    # a parent without the spans: its farm stores the other hosts' spans
    parent = [(2, "recv", 1.0, 2.0), (3, "retire", 1.0, 2.0)]
    assert _read(name, _run(parent, programs=programs)) is None


def test_recorded_excerpt():
    d = json.loads(EXCERPT.read_text())
    spans = [tuple(row) for row in d["host_spans"]]
    r = _run(spans, window=tuple(d["trace_window"]),
             batches=d["traced_batches"], programs=d["devices"][0]["programs"])
    fold = _read("collect_fold_ms.farm", r)
    ready = _read("collect_ready_ms.farm", r)
    assert fold == pytest.approx(214.111786, rel=1e-6)
    assert ready == pytest.approx(26.387754, rel=1e-6)
    # one host thread waits, then folds: together no longer than a batch
    lo, hi = d["trace_window"]
    assert fold + ready <= 1e3 * (hi - lo) / d["traced_batches"]
    assert _read("idle_in_fold_share.farm", r) == pytest.approx(95.836251,
                                                                rel=1e-6)
    # the worker chip's long gaps are the worker host parked
    dev = tracing.device(r.trace_data, 2)
    gaps = tracing.label_gaps(tracing.gaps(dev, lo, hi),
                              [(n, s, e) for h, n, s, e in spans if h == 2])
    assert [name for name, _ in gaps[:3]] == ["park"] * 3
    # every render starts after the worker host's dispatch that launched it
    progs = sorted(p[1] for p in dev["programs"]
                   if p[0] == "jit_stage_group" and lo <= p[1] < hi)
    disp = sorted(s for h, n, s, e in spans
                  if h == 2 and n == "dispatch" and lo <= s < hi)
    assert len(progs) == len(disp) == 12
    assert all(p >= s - 0.5e-3 for s, p in zip(disp, progs))


def test_traced_farm_run_at_test_size(small_run):
    r, out = small_run("mandelbrot-farm.jaxmesh4", seconds=1.5, trace=True)
    assert out["correct"] is True
    m = out["metrics"]
    # the host spans are there on the CPU; the device trace is not
    assert m["collect_fold_ms.farm"]["value"] > 0.0
    assert m["collect_ready_ms.farm"]["value"] > 0.0
    assert "idle_in_fold_share.farm" not in m
    names = {(h, n) for h, n, s, e in r.data["host_spans"]}
    assert {(h, "park") for h in range(4)} <= names
    assert {h for h, n in names if n == "fold"} == {3}
