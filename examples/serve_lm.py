"""Serving demo: continuous-batching farm over a batched decode step.

Mixed-length requests stream through a fixed slot pool (OneFanAny at the
request layer); output equals independent per-request generation.

    PYTHONPATH=src python examples/serve_lm.py --arch qwen2-0.5b
"""

import argparse
import time

import jax

from repro.configs import get_config
from repro.launch.cache import enable_compile_cache
from repro.models import Model
from repro.serve import FarmScheduler, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--slots", type=int, default=4)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sched = FarmScheduler(model, params, n_slots=args.slots, max_len=96)
    for i in range(args.requests):
        sched.submit(Request(rid=i,
                             prompt=[(13 * i + j) % 200 + 1
                                     for j in range(2 + i % 4)],
                             max_new=4 + (i * 3) % 9))
    t0 = time.monotonic()
    done = sched.run()
    dt = time.monotonic() - t0
    toks = sum(len(r.generated) for r in done)
    print(f"[serve_lm] {args.arch}: {len(done)} reqs, {toks} tokens, "
          f"{dt:.2f}s → {toks/dt:.1f} tok/s; "
          f"{sched.steps_run} farm steps, mean occupancy "
          f"{toks/max(sched.steps_run,1):.2f}/{args.slots}")
    for r in sorted(done, key=lambda r: r.rid)[:5]:
        print(f"  req {r.rid}: {r.prompt} → {r.generated}")


if __name__ == "__main__":
    main()
