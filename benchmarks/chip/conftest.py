"""Tests of the chip benchmark's harness, run on the CPU at small sizes."""

import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
for p in (str(HERE), str(HERE.parents[1]), str(HERE.parents[1] / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from harness import common  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
DATA = HERE / "tests" / "data"
MERGED = ("configs", "workloads", "end_to_end", "per_layer")


def bench() -> dict:
    """``BENCHMARK.json`` with the serving cells the tests define for
    themselves (``tests/data/serving.json``)."""
    real = common.benchmark()
    extra = common.load_json(DATA / "serving.json")
    return dict(real, **{k: real[k] + extra[k] for k in MERGED})


def mix_file(name: str) -> dict:
    """A test cell's mix (``tests/data/<name>.json``) or a mix of the
    benchmark's."""
    path = DATA / f"{name}.json"
    return common.load_json(path) if path.is_file() else \
        common.traffic_file(name)


def tiny_model(name: str) -> dict:
    """The configuration's file at sizes a CPU test can hold, wide enough
    that its logits spread about as the full model's do."""
    cfg = dict(common.config_file(bench(), name))
    cfg.update(hidden_size=512, intermediate_size=1024, num_hidden_layers=2,
               num_attention_heads=8, num_key_value_heads=2,
               vocab_size=4096)
    if "rope_scaling" in cfg:
        cfg["rope_scaling"] = {"type": "mrope", "mrope_section": [16, 8, 8]}
    return cfg


def tiny_mix(name: str) -> dict:
    """The mix's file with few slots, short requests and a fast stream."""
    mix = dict(mix_file(name))
    mix.update(n_slots=4, max_len=96, rate_per_s=8.0, trace_s=1.0,
               preroll_s=min(mix.get("preroll_s", 0), 0.5),
               backlog=min(mix.get("backlog", 0), 4),
               prompt_len=dict(mix["prompt_len"], min=4,
                               max=min(mix["prompt_len"]["max"], 40)),
               output_len={"dist": "lognormal", "median": 8, "sigma": 0.5,
                           "min": 2, "max": 40})
    return mix


def tiny_farm() -> dict:
    cfg = dict(common.config_file(common.benchmark(), "mandelbrot-farm"))
    cfg.update(width=128, height=128, bands=8, iters=50, microbatch_size=2)
    return cfg


@pytest.fixture
def small_run():
    """``small_run(cell, seconds=, trace=, fault=)``: a run of the cell at
    test size, with the cell's own limits, and its result line."""
    import run

    def make(cell_name, *, seconds=2.0, trace=False, fault=None, seed=7):
        b = bench()
        cell = common.cell(b, cell_name)
        limits = common.load_json(DATA / "serving.json")["limits"].get(
            cell_name)
        if cell["config"] == "mandelbrot-farm":
            cfg, mix = tiny_farm(), common.traffic_file(cell["traffic"])
        else:
            cfg, mix = tiny_model(cell["config"]), tiny_mix(cell["traffic"])
        r = run.new_run(b, cell, seed=seed, seconds=seconds, trace=trace,
                        cfg=cfg, mix=mix, limits=limits)
        r.fault = fault
        return r, run.execute(r, dict(CPU, count=cell["chips"]))

    return make
