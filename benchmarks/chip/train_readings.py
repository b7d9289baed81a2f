"""Readings that set a training cell's limits, run by hand on the chip.

    python3 benchmarks/chip/train_readings.py <cell> --seeds 1-2 \\
        --control 1-2 --seconds 10 [--plant half_batch:3,skip_update:4]

For each seed, one run of the cell in this process: its check as the timed
path produced it (the lower reading), and on the ``--control`` seeds the
same comparison with the reference computed in the precision below the
configuration's put in the program's place (the upper reading).  Each
``--plant name:seed`` is one more run with a fault of :data:`PLANTS` in the
training step, which its check has to find.  Every result is one JSON line
on standard output, also appended to ``bench_out/calibrate.jsonl``.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import contextlib  # noqa: E402

from calibrate import _run, emit  # noqa: E402
from spread import seed_list  # noqa: E402
from harness import common  # noqa: E402


def _skip_update(step):
    """The step's update dropped: the parameters come back as they went
    in."""
    def faulty(params, opt_state, batch):
        _, opt_state, metrics = step(params, opt_state, batch)
        return params, opt_state, metrics
    return faulty


def _half_batch(step):
    """The step trained on the first half of its batch's sequences."""
    def faulty(params, opt_state, batch):
        return step(params, opt_state, {k: v[:v.shape[0] // 2]
                                        for k, v in batch.items()})
    return faulty


PLANTS = {"skip_update": _skip_update, "half_batch": _half_batch}


@contextlib.contextmanager
def planted(name: str):
    """The program's ``make_train_step`` with fault ``name`` in every step
    ``train`` builds while the context is open."""
    from repro.train import train_loop
    real = train_loop.make_train_step
    train_loop.make_train_step = \
        lambda *a, **k: PLANTS[name](real(*a, **k))
    try:
        yield
    finally:
        train_loop.make_train_step = real


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--plant", default="",
                    help="name:seed,... runs with a fault planted")
    args = ap.parse_args()
    common.setup_paths()
    bench = common.benchmark()
    cell = common.cell(bench, args.cell)
    common.enable_cache()
    info = common.device_info(int(cell["chips"]))
    control = set(seed_list(args.control))
    system = common.load_module(
        "systems", common.config_file(bench, cell["config"])["system"])
    runs = [(seed, None) for seed in seed_list(args.seeds)] + [
        (int(seed), name) for name, seed in
        (x.split(":") for x in args.plant.split(",") if x)]
    for seed, plant in runs:
        with planted(plant) if plant else contextlib.nullcontext():
            r, out = _run(bench, cell, info, seed, args.seconds)
        row = {"readings": cell["name"], "seed": seed, "plant": plant,
               "seconds": args.seconds, "program": out["checks"],
               "correct": out["correct"], "metrics": out["metrics"],
               "memory_peak_bytes": out["device"]["memory_peak_bytes"]}
        if seed in control and not plant:
            row["control"] = system.control_readings(r)
        emit(row)
        del r
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
