"""Serving launcher: a :class:`~repro.serve.ServeEngine` over a local or
clustered decode backend.

    python -m repro.launch.serve --arch qwen2-0.5b --reduced --requests 8
    python -m repro.launch.serve --arch qwen2-0.5b --reduced \\
        --hosts 2 --transport inprocess --n-slots 4 --arrival-rate 20

``--hosts 0`` (default) decodes in-process (:class:`LocalDecodeBackend`);
``--hosts N`` parks the decode farm warm on a
:class:`~repro.cluster.deploy.ClusterDeployment` over ``--transport``,
mirroring ``repro.launch.cluster``'s flags.  ``--arrival-rate R`` replays
an open-loop Poisson arrival trace at R requests/s instead of submitting
everything up front, and the report adds TTFT / per-token latency
percentiles over the completed responses.
"""

from __future__ import annotations

import argparse
import random
import time

from ._common import (add_cluster_flags, add_model_flags, apply_runtime_env,
                      autoscale_policy)
from .cache import enable_compile_cache


def _pct(xs: list, q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) of a non-empty list."""
    ys = sorted(xs)
    return ys[min(len(ys) - 1, int(len(ys) * q / 100.0))]


def main():
    ap = argparse.ArgumentParser()
    add_model_flags(ap)
    add_cluster_flags(ap, default_hosts=0, default_transport="inprocess")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--n-slots", "--slots", dest="n_slots", type=int,
                    default=4, help="decode slot-batch width")
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="open-loop Poisson arrivals per second "
                         "(0 = submit everything up front)")
    ap.add_argument("--seed", type=int, default=0,
                    help="arrival-trace seed")
    args = ap.parse_args()
    apply_runtime_env(args)
    enable_compile_cache()

    import jax

    from repro.configs import get_config
    from repro.serve import (ClusterDecodeBackend, LocalDecodeBackend,
                             Request, ServeEngine)

    cfg = get_config(args.arch, reduced=args.reduced)
    if args.hosts > 0:
        shards = max(s for s in range(1, min(args.hosts, args.n_slots) + 1)
                     if args.n_slots % s == 0)
        backend = ClusterDecodeBackend(
            ("model", args.arch, args.reduced), n_slots=args.n_slots,
            shards=shards, hosts=args.hosts, transport=args.transport,
            max_len=args.max_len, autoscale=autoscale_policy(args))
        where = f"cluster[{args.transport}x{args.hosts}h/{shards} shards]"
    else:
        from repro.models import Model
        model = Model(cfg)
        backend = LocalDecodeBackend(model, model.init(jax.random.PRNGKey(0)),
                                     n_slots=args.n_slots,
                                     max_len=args.max_len)
        where = "local"

    reqs = [Request(
        rid=i,
        prompt=tuple((7 * i + j) % (cfg.vocab - 1) + 1
                     for j in range(3 + i % 5)),
        max_new=args.max_new // 2 + (i % args.max_new) // 2 + 1)
        for i in range(args.requests)]
    rng = random.Random(args.seed)
    due, t = [], 0.0
    for _ in reqs:
        if args.arrival_rate > 0:
            t += rng.expovariate(args.arrival_rate)
        due.append(t)

    t0 = time.monotonic()
    with ServeEngine(backend) as eng:
        i = 0
        while i < len(reqs) or eng.pending or eng._live:
            now = time.monotonic() - t0
            while i < len(reqs) and due[i] <= now:
                eng.submit(reqs[i])
                i += 1
            if eng.pending or eng._live:
                eng.step()
            elif i < len(reqs):
                time.sleep(max(0.0, due[i] - (time.monotonic() - t0)))
        done = list(eng.completed)
        dt = time.monotonic() - t0
        toks = sum(len(r.tokens) for r in done)
        steps = eng.steps_run
    print(f"[serve] {args.arch} ({where}): {len(done)} requests, {toks} "
          f"tokens in {dt:.2f}s ({toks / max(dt, 1e-9):.1f} tok/s) over "
          f"{steps} farm steps "
          f"(mean occupancy {toks / max(steps, 1):.2f}/{args.n_slots})")
    for aev in getattr(backend, "autoscale_events", []):
        print(f"[serve] {aev.describe()}")
    ttfts = [r.ttft * 1e3 for r in done]
    tpots = [r.tpot * 1e3 for r in done if len(r.tokens) > 1]
    if ttfts:
        line = (f"[serve] ttft p50 {_pct(ttfts, 50):.1f}ms "
                f"p99 {_pct(ttfts, 99):.1f}ms")
        if tpots:
            line += (f" | tpot p50 {_pct(tpots, 50):.2f}ms "
                     f"p99 {_pct(tpots, 99):.2f}ms")
        print(line)
    for r in done[:4]:
        print(f"  req {r.rid}: prompt {list(r.prompt)} -> {list(r.tokens)} "
              f"[{r.finish_reason}]")


if __name__ == "__main__":
    main()
