"""Repeat a cell's runs and report the spread of each metric.

    python3 benchmarks/chip/spread.py <cell> --seeds 101-106 --sets 2 \\
        --seconds 51 [--traced 201-203]

Runs ``run.py`` once per seed and set, each in a process of its own (this
parent never touches JAX, so each child has the chip), then the ``--traced``
seeds with ``--trace 1``.  Every result line goes to
``bench_out/spread_<cell>.jsonl``; the summary gives, per set and metric,
the median and the spread: the distance between the first and third
quartiles (``statistics.quantiles(n=4)``) as a share of the median.  Stops
at the first run that exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
OUT = ROOT / "bench_out"


def seed_list(spec: str) -> list:
    out = []
    for part in spec.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += list(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def one(cell: str, seed: int, seconds: float, trace: int, log) -> dict:
    t = time.perf_counter()
    p = subprocess.run([sys.executable, str(HERE / "run.py"), "--workload",
                        cell, "--seed", str(seed), "--seconds", str(seconds),
                        "--trace", str(trace)], capture_output=True,
                       text=True, cwd=str(ROOT))
    row = {"cell": cell, "seed": seed, "trace": trace, "rc": p.returncode,
           "wall_s": time.perf_counter() - t,
           "stderr_tail": p.stderr[-6000:]}
    if p.returncode == 0 and p.stdout.strip():
        row["result"] = json.loads(p.stdout.strip().splitlines()[-1])
    log.write(json.dumps(row) + "\n")
    log.flush()
    res = row.get("result", {})
    print(json.dumps({k: row[k] for k in ("seed", "trace", "rc", "wall_s")}
                     | {"correct": res.get("correct"),
                        "metrics": {k: v["value"] for k, v in
                                    res.get("metrics", {}).items()},
                        "checks": res.get("checks")}), flush=True)
    return row


def spread(values: list) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--traced", default="")
    args = ap.parse_args()
    OUT.mkdir(exist_ok=True)
    sets = []
    with open(OUT / f"spread_{args.cell}.jsonl", "a") as log:
        for _ in range(args.sets):
            rows = []
            for s in seed_list(args.seeds):
                rows.append(one(args.cell, s, args.seconds, 0, log))
                if rows[-1]["rc"] != 0:
                    return 1
            sets.append(rows)
        for s in seed_list(args.traced):
            if one(args.cell, s, args.seconds, 1, log)["rc"] != 0:
                return 1
    for i, rows in enumerate(sets):
        res = [r["result"] for r in rows]
        names = sorted({k for x in res for k in x["metrics"]})
        for m in names:
            v = [x["metrics"][m]["value"] for x in res if m in x["metrics"]]
            print(json.dumps({"set": i, "metric": m, "n": len(v),
                              "median": statistics.median(v),
                              "spread": spread(v) if len(v) >= 2 else None,
                              "values": v}), flush=True)
        print(json.dumps({"set": i, "correct": [x["correct"] for x in res],
                          "checks": [x["checks"] for x in res]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
