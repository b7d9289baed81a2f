"""Measurements that set the benchmark's numbers, run by hand on the chip.

    python3 benchmarks/chip/calibrate.py sweep <cell> --rates 1,2,4 --seconds 30
    python3 benchmarks/chip/calibrate.py probe <cell> --seconds 12
    python3 benchmarks/chip/calibrate.py readings <cell> --seeds 1-12 \\
        --control 1-3 --seconds 51

* ``sweep``: the cell's traffic at each offered rate (requests/s); prints
  what was completed, the backlog at the close and the latency tails.  The
  highest rate without a growing backlog is the knee.
* ``probe``: one traced run whose profile is summarised (every plane, line,
  event count and the commonest names) into ``bench_out/``, with a small
  excerpt of the reduced trace for the reduction's tests.
* ``readings``: the numbers a cell's limits are set from, in one process:
  each seed's check as the timed path produced it (the lower reading), and
  on the ``--control`` seeds the same comparison with the reference computed
  in the precision below the configuration's (the upper reading).

Every result is one JSON line on standard output, also appended to
``bench_out/calibrate.jsonl``.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402
from spread import seed_list  # noqa: E402
from harness import common  # noqa: E402

OUT = common.ROOT / "bench_out"


def emit(row: dict) -> None:
    OUT.mkdir(exist_ok=True)
    line = json.dumps(row)
    print(line, flush=True)
    with open(OUT / "calibrate.jsonl", "a") as f:
        f.write(line + "\n")


def _run(bench, cell, info, seed, seconds, trace=False, mix=None,
         log_dir=None):
    r = run.new_run(bench, cell, seed=seed, seconds=seconds, trace=trace,
                    mix=mix)
    system = common.load_module("systems", r.cfg["system"])
    if log_dir is not None:
        r.log_dir = log_dir
        system.run(r)
        return r, run.result(r, info)
    return r, run.execute(r, info)


def sweep(bench, cell, info, args) -> None:
    import numpy as np
    base = common.traffic_file(cell["traffic"])
    for rate in [float(x) for x in args.rates.split(",")]:
        mix = dict(base, rate_per_s=rate, drain_s=args.drain)
        t = time.perf_counter()
        r, out = _run(bench, cell, info, args.seed, args.seconds, mix=mix)
        d = r.data
        ttft = np.asarray(d["ttft_s"]) if d.get("ttft_s") else np.zeros(1)
        emit({"sweep": cell["name"], "rate_per_s": rate,
              "seconds": args.seconds, "attempted": r.attempted,
              "failed": r.failed, "finished": d["finished"],
              "finished_per_s": d["finished_in_window"] / d["window_s"],
              "tokens_per_s": d["tokens_in_window"] / d["window_s"],
              "ttft_p50_ms": 1e3 * float(np.percentile(ttft, 50)),
              "ttft_p90_ms": 1e3 * float(np.percentile(ttft, 90)),
              "itl_p95_ms": 1e3 * float(np.percentile(d["itl_s"], 95))
              if d["itl_s"] else None,
              "late_max_ms": 1e3 * d["generator_late_max_s"],
              "setup_s": r.setup_s, "correct": out["correct"],
              "checks": out["checks"], "wall_s": time.perf_counter() - t})


def probe(bench, cell, info, args) -> None:
    from jax.profiler import ProfileData
    d = OUT / f"probe_{cell['name']}"
    shutil.rmtree(d, ignore_errors=True)
    r, out = _run(bench, cell, info, args.seed, args.seconds, trace=True,
                  log_dir=d)
    files = sorted(d.glob("**/*.xplane.pb"))
    summary = []
    if files:
        pd = ProfileData.from_file(str(files[-1]))
        for plane in pd.planes:
            lines = []
            for line in plane.lines:
                names: dict = {}
                n = 0
                for ev in line.events:
                    n += 1
                    names[ev.name] = names.get(ev.name, 0) + 1
                top = sorted(names.items(), key=lambda kv: -kv[1])[:12]
                lines.append({"line": line.name, "events": n, "top": top})
            summary.append({"plane": plane.name, "lines": lines})
        size = files[-1].stat().st_size
    else:
        size = 0
    excerpt = r.trace_data or {"devices": []}
    lo, hi = r.data["trace_window"]
    small = {"offset_s": excerpt.get("offset_s", 0.0), "window": [lo, hi],
             "devices": excerpt["devices"]}
    (OUT / f"excerpt_{cell['name']}.json").write_text(json.dumps(small))
    (OUT / f"probe_{cell['name']}.json").write_text(
        json.dumps(summary, indent=1))
    shutil.rmtree(d, ignore_errors=True)
    emit({"probe": cell["name"], "xplane_bytes": size, "result": out})


def readings(bench, cell, info, args) -> None:
    control = set(seed_list(args.control))
    if cell["chips"] > 1:   # the farm: exact limits; the control on one chip
        for seed in sorted(control):
            emit({"readings": cell["name"], "seed": seed,
                  "control": farm_control(bench, cell, seed)})
        return
    for seed in seed_list(args.seeds):
        r, out = _run(bench, cell, info, seed, args.seconds)
        row = {"readings": cell["name"], "seed": seed,
               "seconds": args.seconds, "program": out["checks"],
               "correct": out["correct"], "metrics": out["metrics"]}
        if seed in control:
            row["control"] = control_reading(r)
        emit(row)
        del r           # its weights, before the next seed makes its own
        gc.collect()


def control_reading(r) -> dict:
    """The check with the reference in the precision below the
    configuration's put in the program's place, on the same sample."""
    ref = common.load_module("references", r.cfg["family"])
    sv = common.load_module("systems", "serve")
    return sv.gap_readings(ref, r.cfg, r.weights, r.check_served,
                           mode="fp8")


def farm_control(bench, cell, seed: int, n: int = 3) -> dict:
    """Pixels where the reference in bfloat16 differs from it in float32,
    over ``n`` batches of the seed's image offsets (one chip)."""
    import jax.numpy as jnp
    import numpy as np
    cfg = common.config_file(bench, cell["config"])
    ref = common.load_module("references", cfg["family"])
    W, H, B, I = (int(cfg[k]) for k in ("width", "height", "bands", "iters"))
    delta = float(cfg["span"]) / W
    bad = 0
    for k in range(n):
        top = int(np.random.default_rng(np.random.SeedSequence(
            [int(seed), 0xFA4A, k])).integers(-(H // 4), H // 4 + 1))
        rows = jnp.asarray(top + np.arange(H), jnp.int32)
        a = np.asarray(ref.counts(rows, W, float(cfg["x0"]),
                                  float(cfg["y0"]), delta, I))
        b = np.asarray(ref.counts(rows, W, float(cfg["x0"]),
                                  float(cfg["y0"]), delta, I, "bfloat16"))
        bad += int(np.sum(a != b))
    return {"mismatched_pixels": bad, "pixels": n * H * W}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("what", choices=("sweep", "probe", "readings"))
    ap.add_argument("cell")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rates", default="")
    ap.add_argument("--drain", type=float, default=0.0)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", default="")
    args = ap.parse_args()
    common.setup_paths()
    bench = common.benchmark()
    cell = common.cell(bench, args.cell)
    common.enable_cache()
    chips = 1 if args.what == "readings" else int(cell["chips"])
    info = common.device_info(chips)
    {"sweep": sweep, "probe": probe, "readings": readings}[args.what](
        bench, cell, info, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
