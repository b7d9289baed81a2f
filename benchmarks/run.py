"""Benchmark runner: one function per paper table.

Prints ``name,us_per_call,derived`` CSV rows (scaffold contract).
Usage: PYTHONPATH=src python -m benchmarks.run [--only tN]
       PYTHONPATH=src python -m benchmarks.run --smoke   # small stream bench,
                                                         # writes BENCH_stream.json
"""

from __future__ import annotations

import argparse
import json
import sys
import traceback


def _smoke() -> None:
    """CI smoke lane: the stream benchmark at reduced size, archived as
    BENCH_stream.json (the perf trajectory's first data point)."""
    from . import stream as stream_bench

    rows = stream_bench.run(smoke=True)
    print("name,us_per_call,derived")
    blob = []
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")
        blob.append({"name": name, "us_per_call": us, "derived": derived})
    with open("BENCH_stream.json", "w") as f:
        json.dump({"benchmark": "stream", "mode": "smoke", "rows": blob},
                  f, indent=2)
    print("wrote BENCH_stream.json", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on table fn names (e.g. t4)")
    ap.add_argument("--smoke", action="store_true",
                    help="small stream benchmark only; writes BENCH_stream.json")
    args = ap.parse_args()

    if args.smoke:
        _smoke()
        return

    from . import cluster as cluster_bench
    from . import tables
    from . import stream as stream_bench

    fns = list(tables.ALL_TABLES) + [stream_bench.run, cluster_bench.run]
    print("name,us_per_call,derived")
    failures = 0
    for fn in fns:
        if args.only and args.only not in fn.__name__:
            continue
        try:
            for name, us, derived in fn():
                print(f"{name},{us:.2f},{derived}")
                sys.stdout.flush()
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{fn.__name__},NaN,ERROR:{e!r}")
            traceback.print_exc(file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
