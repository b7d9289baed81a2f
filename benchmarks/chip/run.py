"""Run one cell of ``BENCHMARK.json`` once, on the chip.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

The cell names a configuration (``configs/<config>.json``, whose ``system``
picks ``systems/<system>.py`` and whose ``family`` picks
``references/<family>.py``), a traffic mix (``traffic/<mix>.json``) and, by
``BENCHMARK.json``, the metrics it reports (``metrics/<metric>.py``); the
limits its check holds the program to are in ``limits/<cell>.json``.  With
``--trace 0`` it prints the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics read from a profiled stretch of the window.

It exits non-zero and prints no result when JAX's first device is not a TPU
or there are fewer chips than the cell needs.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: each number compared with its limit (also the last lines of
standard error).
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse   # noqa: E402
import json       # noqa: E402
import sys        # noqa: E402
import tempfile   # noqa: E402
import types      # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import common, tracing  # noqa: E402


def new_run(bench: dict, cell: dict, *, seed: int, seconds: float,
            trace: bool, cfg: dict | None = None, mix: dict | None = None,
            limits: dict | None = None, t0: float | None = None):
    """Everything one run reads and writes.  ``cfg``, ``mix`` and ``limits``
    default to the cell's files; tests pass smaller ones."""
    return types.SimpleNamespace(
        bench=bench, cell=cell, chips=int(cell["chips"]), seed=int(seed),
        seconds=float(seconds), trace=bool(trace),
        cfg=cfg if cfg is not None else common.config_file(bench,
                                                           cell["config"]),
        mix=mix if mix is not None else common.traffic_file(cell["traffic"]),
        limits=limits if limits is not None else common.load_json(
            common.BENCH_DIR / "limits" / f"{cell['name']}.json"),
        clock=common.Clock(t0), compiles=common.CompileCount(), fault=None,
        log_dir=None, setup_s=None, data={}, checks=[], correct=False,
        attempted=0, failed=0, memory_peak=0, trace_data=None,
        devices_used=[0], weights=None, peak=None)


def execute(r, info: dict) -> dict:
    """Drive the run (set-up, window, check) and build its result line."""
    system = common.load_module("systems", r.cfg["system"])
    if r.trace:
        with tempfile.TemporaryDirectory(prefix="chipbench_trace_") as d:
            r.log_dir = Path(d)
            system.run(r)
    else:
        system.run(r)
    return result(r, info)


def result(r, info: dict) -> dict:
    if info["platform"] == "tpu":
        r.peak = common.peaks(info["kind"])
    metrics = {}
    for m in common.metrics_of(r.bench, r.cell["name"], r.trace):
        v = common.load_module("metrics", m["name"]).read(r)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = dict(info, memory_peak_bytes=int(r.memory_peak))
    out = {"correct": bool(r.correct), "attempted": int(r.attempted),
           "failed": int(r.failed), "metrics": metrics, "device": device}
    if r.trace and r.trace_data is not None:
        lo, hi = r.data["trace_window"]
        devs = [tracing.device(r.trace_data, i) for i in r.devices_used]
        devs = [d for d in devs if d is not None]
        if devs:
            device["busy_s"] = sum(tracing.busy_s(d, lo, hi)
                                   for d in devs) / len(devs)
            device["window_s"] = hi - lo
            focus = tracing.device(r.trace_data,
                                   r.data.get("worker_device", 0)) or devs[0]
            spans = r.data.get("spans") or [
                (name, s, e) for h, name, s, e in r.data.get("host_spans", ())
                if h == r.data.get("worker_device")]
            out["breakdown"] = {
                "device_ops": tracing.top_programs(devs, lo, hi),
                "idle_gaps": tracing.label_gaps(
                    tracing.gaps(focus, lo, hi), spans)}
    out["checks"] = {name: {"value": value, "limit": limit}
                     for name, value, limit in r.checks}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    common.setup_paths()
    try:
        bench = common.benchmark()
        cell = common.cell(bench, args.workload)
        r = new_run(bench, cell, seed=args.seed, seconds=args.seconds,
                    trace=args.trace, t0=T0)
        common.enable_cache()
        info = common.device_info(r.chips)
    except common.BenchError as e:
        common.log(f"error: {e}")
        return 2
    out = execute(r, info)
    for name, c in out["checks"].items():
        common.log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
