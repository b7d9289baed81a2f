"""The reduction from a device trace to numbers, on a small recorded trace
(``data/trace_excerpt.json``: a quarter second of a v5e's trace of the
qwen2-0.5b chat cell, reduced by ``tracing.load`` to program executions)
and on hand-made rows."""

import json
from pathlib import Path

import pytest

from harness import tracing

DATA = Path(__file__).resolve().parent / "data" / "trace_excerpt.json"


def _dev(programs):
    return {"name": "/device:TPU:0", "programs": [list(p) for p in programs]}


def test_busy_is_the_union_of_overlapping_ops():
    dev = _dev([("a", 0.0, 1.0), ("b", 0.5, 1.0), ("c", 3.0, 1.0)])
    assert tracing.busy_s(dev, 0.0, 10.0) == pytest.approx(2.5)
    # clipped to the window
    assert tracing.busy_s(dev, 1.0, 3.5) == pytest.approx(1.0)
    assert tracing.gaps(dev, 0.0, 5.0) == [(1.5, 3.0), (4.0, 5.0)]
    assert tracing.gaps(dev, -1.0, 4.0) == [(-1.0, 0.0), (1.5, 3.0)]


def test_program_time_by_name_and_start():
    dev = _dev([("jit__decode", 0.0, 0.004), ("jit__prefill", 0.01, 0.03),
                ("jit__decode", 0.05, 0.005), ("jit__decode", 2.0, 1.0)])
    t, n = tracing.program_time(dev, "jit__decode", 0.0, 1.0)
    assert n == 2 and t == pytest.approx(0.009)
    t, n = tracing.program_time(dev, "", 0.0, 1.0)
    assert n == 3 and t == pytest.approx(0.039)
    assert tracing._program_name("jit__decode(1234)") == "jit__decode"


def test_top_programs_and_labelled_gaps():
    d0 = _dev([("fusion.1", 0.0, 0.2), ("copy", 0.3, 0.1)])
    d1 = _dev([("fusion.1", 0.0, 0.4)])
    top = tracing.top_programs([d0, d1], 0.0, 1.0)
    assert top[0] == ["fusion.1", pytest.approx(0.3)]
    assert top[1] == ["copy", pytest.approx(0.05)]
    spans = [("decode_chunk", 0.19, 0.31), ("prefill", 0.5, 0.9),
             ("step", 0.0, 1.0)]
    gaps = tracing.label_gaps(tracing.gaps(d0, 0.0, 1.0), spans)
    assert gaps == [["prefill", pytest.approx(0.6)],
                    ["decode_chunk", pytest.approx(0.1)]]
    assert tracing.label_gaps([(2.0, 3.0)], spans) == [
        ["no host span", pytest.approx(1.0)]]


def test_device_by_index():
    trace = {"devices": [_dev([]), dict(_dev([]), name="/device:TPU:2")]}
    assert tracing.device(trace, 2)["name"] == "/device:TPU:2"
    assert tracing.device(trace, 3) is None


def test_recorded_excerpt():
    trace = json.loads(DATA.read_text())
    lo, hi = trace["window"]
    dev = tracing.device(trace, 0)
    assert dev is not None and dev["programs"]
    busy = tracing.busy_s(dev, lo, hi)
    assert 0.0 < busy <= hi - lo
    idle = sum(e - s for s, e in tracing.gaps(dev, lo, hi))
    assert busy + idle == pytest.approx(hi - lo)
    # the prefill chunks keep the device all but busy: back to back, each
    # about 54 ms for 8 prompt tokens
    t, n = tracing.program_time(dev, "jit__prefill", lo, hi)
    assert n >= 3 and 0.04 < t / n < 0.07
    assert busy / (hi - lo) > 0.9
    # programs that started in the window, counted whole, cover its busy
    # time (the last runs on past its end)
    assert t + tracing.program_time(dev, "jit_convert", lo, hi)[0] >= busy
