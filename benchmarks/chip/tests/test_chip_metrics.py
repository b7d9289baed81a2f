"""Each metric's reader on hand-made run data: the number it reads, and
nothing (never 0) where there is nothing to read."""

import types

import pytest

from conftest import bench
from harness import common, costs

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _run(**data):
    cfg = common.config_file(bench(), "qwen2-0.5b")
    return types.SimpleNamespace(cfg=cfg, data=data, trace_data=None,
                                 peak=PEAK, setup_s=12.5)


def _read(name, r):
    return common.load_module("metrics", name).read(r)


def test_every_metric_reads_nothing_from_an_empty_run():
    b = bench()
    r = _run()
    r.setup_s = None
    for m in b["end_to_end"] + b["per_layer"]:
        assert _read(m["name"], r) is None, m["name"]


def test_end_to_end_readers():
    r = _run(ttft_s=[0.1 * i for i in range(1, 11)], tokens_in_window=5100,
             window_s=51.0, batches=150)
    assert _read("ttft_p90_ms", r) == pytest.approx(910.0)
    assert _read("tokens_per_s", r) == pytest.approx(100.0)
    assert _read("batch_ms", r) == pytest.approx(340.0)
    assert _read("setup_s", r) == 12.5


def test_host_span_readers():
    r = _run(queue_wait_s=[0.01, 0.02, 0.03], occupancy=[1.0, 0.5],
             itl_s=[0.004] * 19 + [0.1])
    assert _read("queue_wait_p50_ms.chat", r) == pytest.approx(20.0)
    assert _read("slot_occupancy.decode", r) == pytest.approx(75.0)
    assert _read("itl_p95_ms.chat", r) == pytest.approx(4.0 + 0.05 * 96.0)
    r = _run(host_spans=[(2, "recv", 0.0, 1.0), (2, "recv", 9.5, 11.0),
                         (1, "recv", 0.0, 10.0), (2, "send", 0.0, 10.0)],
             trace_window=(0.0, 10.0), worker_device=2)
    assert _read("worker_recv_share.farm", r) == pytest.approx(15.0)


def _trace(programs):
    return {"devices": [{"name": "/device:TPU:0", "programs": programs}]}


def test_device_trace_readers():
    calls = [[100, 200], [101, 201]]
    progs = [["jit__decode", 0.0, 0.004], ["jit__prefill", 0.01, 0.03],
             ["jit__decode", 0.05, 0.006]]
    r = _run(trace_window=(0.0, 0.1), window=(0.0, 0.1),
             decodes=[(0.004, calls[0]), (0.056, calls[1])],
             prefills=[(0.04, 0, 8)])
    r.trace_data = _trace(progs)
    assert _read("decode_step_ms.decode", r) == pytest.approx(5.0)
    assert _read("prefill_share.chat", r) == pytest.approx(75.0)
    assert _read("idle_share.chat", r) == pytest.approx(60.0)
    least = sum(costs.least_time(*costs.decode_step_cost(r.cfg, c),
                                 PEAK)[0] for c in calls)
    assert _read("decode_roofline.decode", r) == pytest.approx(
        100 * least / 0.010)
    assert r.data["decode_bound"] == "memory"
    flops = sum(costs.token_flops(r.cfg, c) for c in calls[0] + calls[1]) \
        + sum(costs.token_flops(r.cfg, j, logits=False) for j in range(1, 9))
    assert _read("mfu.decode", r) == pytest.approx(
        100 * flops / (0.1 * 197e12))
    # host and device disagree on the number of calls: no roofline
    r.data["decodes"] = r.data["decodes"] * 3
    assert _read("decode_roofline.decode", r) is None


def test_farm_device_readers():
    r = _run(trace_window=(0.0, 1.0), worker_device=2, traced_batches=4)
    r.trace_data = {"devices": [
        {"name": "/device:TPU:2", "programs": [["jit_render", 0.1, 0.8]]}]}
    assert _read("render_ms.farm", r) == pytest.approx(200.0)
    assert _read("idle_share.farm", r) == pytest.approx(20.0)
