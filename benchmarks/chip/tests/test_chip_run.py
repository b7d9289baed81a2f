"""The command, the registry of named files, and whole runs at test size."""

import json
import os
import subprocess
import sys

import pytest

from conftest import bench
from harness import common

RUN = str(common.BENCH_DIR / "run.py")


def test_command_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, RUN, "--workload",
                        "mandelbrot-farm.jaxmesh4",
                        "--seed", str(2 ** 31 + 9), "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True,
                       env=env, cwd=str(common.ROOT), timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_every_cell_finds_its_files_by_name():
    bench = common.benchmark()
    names = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    for m in names:
        assert hasattr(common.load_module("metrics", m), "read"), m
    for cell in bench["workloads"]:
        cfg = common.config_file(bench, cell["config"])
        common.traffic_file(cell["traffic"])
        common.load_module("systems", cfg["system"])
        common.load_module("references", cfg["family"])
        common.load_json(common.BENCH_DIR / "limits" / f"{cell['name']}.json")
        e2e = common.metrics_of(bench, cell["name"], trace=False)
        layer = common.metrics_of(bench, cell["name"], trace=True)
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert layer
        moved = {m["moves"] for m in layer}
        assert moved <= {m["name"] for m in e2e}


def test_a_new_cell_needs_only_new_files():
    """A cell whose configuration and mix are found by name alone: entries
    in BENCHMARK.json that name files already under the benchmark."""
    real = common.benchmark()
    config = {"name": "qwen2-vl-2b", "source": "test",
              "file": "benchmarks/chip/configs/qwen2-vl-2b.json",
              "reduced": [], "why": "test"}
    cell = {"name": "qwen2-vl-2b.closed", "config": "qwen2-vl-2b",
            "traffic": "closed-loop", "chips": 1, "why": "test"}
    b = dict(real, configs=real["configs"] + [config],
             workloads=real["workloads"] + [cell])
    cfg = common.config_file(b, cell["config"])
    assert cfg["num_hidden_layers"] == 28
    common.load_module("systems", cfg["system"])
    common.load_module("references", cfg["family"])
    assert common.traffic_file(cell["traffic"])["sample"] > 0
    e2e = common.metrics_of(b, cell["name"], trace=False)
    # end-to-end metrics without a workloads list reach every cell
    assert [m["name"] for m in e2e] == ["setup_s"]


@pytest.mark.parametrize("cell", ["qwen2-0.5b.chat", "qwen2-vl-2b.decode"])
def test_serving_run_at_test_size(small_run, cell):
    r, out = small_run(cell, seconds=2.0)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    want = {m["name"] for m in common.metrics_of(bench(), cell, False)}
    assert set(out["metrics"]) == want
    assert list(out)[-1] == "checks"
    gap = out["checks"]["max_logit_gap"]
    assert 0.0 <= gap["value"] <= gap["limit"]
    json.dumps(out)


def test_traced_serving_run_reads_the_engine_spans(small_run):
    r, out = small_run("qwen2-vl-2b.decode", seconds=2.0, trace=True)
    assert out["correct"] is True
    m = out["metrics"]
    # the host-side readers find their spans; the device readers need a
    # chip's trace and report nothing on the CPU
    assert 0.0 < m["slot_occupancy.decode"]["value"] <= 100.0
    assert m["itl_p95_ms.decode"]["value"] > 0.0
    assert "decode_roofline.decode" not in m and "mfu.decode" not in m


def test_farm_run_at_test_size(small_run):
    r, out = small_run("mandelbrot-farm.jaxmesh4", seconds=1.5)
    assert out["correct"] is True
    assert out["checks"]["mismatched_pixels"] == {"value": 0, "limit": 0}
    assert out["checks"]["misdelivered_bands"] == {"value": 0, "limit": 0}
    assert out["metrics"]["batch_ms"]["value"] > 0.0
    assert out["attempted"] == r.data["batches"]
