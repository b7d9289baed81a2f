"""Cluster launcher: one GPP network, many hosts (paper §7).

    python -m repro.launch.cluster --hosts 2 --transport pipe --instances 16

Partitions the demo workload (a Mandelbrot row-band farm or a two-stage
pipeline) over ``--hosts`` simulated hosts, proves via the CSP checker that
the partitioned network trace-refines the unpartitioned one, streams the
work through one executor per host, verifies the result bit-identical to the
sequential oracle, and prints the cross-host netlog report.
"""

from __future__ import annotations

import argparse

from ._common import add_cluster_flags, apply_runtime_env, autoscale_policy
from .cache import enable_compile_cache


# module-level factories: the pipe transport spawns fresh interpreters that
# rebuild the network from a picklable (callable, args) recipe

def make_mandelbrot(bands: int, height: int, width: int, iters: int):
    import jax.numpy as jnp
    from repro.core import DataParallelCollect
    from repro.kernels.mandelbrot import ref

    band_h = height // bands
    delta = 3.0 / width

    def create(i):
        return jnp.asarray(i * band_h, jnp.int32)

    def render(row0):
        # the shared escape-time oracle, offset to this band's top row
        return ref.mandelbrot(band_h, width, x0=-2.2,
                              y0=-1.15 + delta * row0, pixel_delta=delta,
                              max_iterations=iters)

    return DataParallelCollect(
        create=create, function=render,
        collector=lambda acc, cnt: acc + jnp.sum(cnt),
        init=jnp.asarray(0, jnp.int32), workers=bands, jit_combine=True,
        name="mandelbrot")


def make_pipeline(scale: float):
    import jax.numpy as jnp
    from repro.core import OnePipelineCollect
    return OnePipelineCollect(
        create=lambda i: jnp.asarray(float(i)),
        stage_ops=[lambda x: x * x, lambda x: x * scale + 1.0],
        collector=lambda a, x: a + x, init=jnp.asarray(0.0),
        jit_combine=True, name="pipeline")


def main():
    ap = argparse.ArgumentParser()
    add_cluster_flags(ap, default_hosts=2, default_transport="pipe")
    ap.add_argument("--workload", default="mandelbrot",
                    choices=["mandelbrot", "pipeline"])
    ap.add_argument("--instances", type=int, default=8)
    ap.add_argument("--microbatch", type=int, default=2)
    ap.add_argument("--bands", type=int, default=8)
    ap.add_argument("--size", type=int, default=64)
    ap.add_argument("--iters", type=int, default=40)
    ap.add_argument("--batches", type=int, default=1,
                    help="batches through ONE warm deployment (batch 0 "
                         "pays spawn+compile; the rest are steady-state)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="record per-host trace rings, merge them on the "
                         "controller and export Chrome trace-event JSON "
                         "to PATH (open in chrome://tracing or Perfetto)")
    ap.add_argument("--snapshot-every", type=int, default=0, metavar="N",
                    help="durable deployment: snapshot each host's fold "
                         "state every N chunks and the controller meta at "
                         "batch boundaries (needs --snapshot-dir)")
    ap.add_argument("--snapshot-dir", metavar="DIR", default=None,
                    help="where the durable deployment state lives")
    ap.add_argument("--resume-from", metavar="DIR", default=None,
                    help="ADOPT a previous run's durable state from DIR "
                         "instead of deploying fresh: bump the epoch, "
                         "re-prove the §6.1.1 refinement, replay any "
                         "pending batch from the fold snapshots, then "
                         "serve --batches more")
    ap.add_argument("--cut", default="count", choices=["count", "cost"],
                    help="partition objective: 'count' balances process "
                         "COUNTS per host (the §6 default); 'cost' runs a "
                         "short seeded calibration and minimises the "
                         "bottleneck host's measured TIME, cut-channel "
                         "transfer included — the plan is still proved as "
                         "a §6.1.1 refinement before anything deploys")
    ap.add_argument("--calibrate", action="store_true",
                    help="print the measured per-process cost profile "
                         "(wall time, output bytes, flops prior) and the "
                         "calibrated transport bandwidth before deploying")
    ap.add_argument("--coalesce-bytes", type=int, default=0, metavar="B",
                    help="transport fast path: coalesce small records into "
                         "one ring slot / one pipe write, up to B bytes "
                         "per flush (0 = per-record sends, the default)")
    args = ap.parse_args()
    apply_runtime_env(args)
    enable_compile_cache()

    import time

    from repro.cluster import ClusterDeployment, check_refinement, partition
    from repro.core import netlog, run_sequential

    if args.workload == "mandelbrot":
        factory = (make_mandelbrot,
                   (args.bands, args.size, args.size, args.iters))
        instances = args.bands
    else:
        factory = (make_pipeline, (2.0,))
        instances = args.instances
    net = factory[0](*factory[1])
    seq = run_sequential(net, instances)
    same = True

    def _same(out):
        return all(bool((out[k] == seq[k]).all()
                        if hasattr(seq[k], "all") else out[k] == seq[k])
                   for k in seq)

    if args.resume_from:
        dep = ClusterDeployment.adopt(args.resume_from, factory=factory,
                                      transport=args.transport,
                                      trace=bool(args.trace))
        plan = dep.plan
        ev = dep.events[-1]
        print(plan.describe())
        print(f"[cluster] adopted durable deployment from "
              f"{args.resume_from}: epoch {dep.epoch}, "
              f"refined={ev.refined}", flush=True)
        if ev.refined is not True:
            raise SystemExit(1)
    else:
        profile = None
        if args.cut == "cost" or args.calibrate:
            from repro.cluster import calibrate
            t0 = time.perf_counter()
            profile = calibrate(net, instances=instances,
                                microbatch_size=args.microbatch,
                                transports=(args.transport,))
            print(f"[cluster] calibrated {len(profile.costs)} process "
                  f"cost(s) in {(time.perf_counter() - t0) * 1e3:.1f}ms")
            if args.calibrate:
                print(profile.describe())
        if args.cut == "cost":
            from repro.cluster import cost_assignment
            plan = partition(net, assignment=cost_assignment(
                net, args.hosts, profile, transport=args.transport))
        else:
            plan = partition(net, hosts=args.hosts)
        print(plan.describe())
        print(f"[cluster] CSP refinement (partitioned [T= unpartitioned, "
              f"both directions): {check_refinement(net, plan)}")
        dep = ClusterDeployment(net, plan=plan, transport=args.transport,
                                microbatch_size=args.microbatch,
                                factory=factory, trace=bool(args.trace),
                                snapshot_every=args.snapshot_every,
                                snapshot_dir=args.snapshot_dir,
                                coalesce_bytes=args.coalesce_bytes,
                                profile=profile,
                                autoscale=autoscale_policy(args))
    with dep:
        if args.resume_from and dep.controller._needs_recovery:
            t0 = time.perf_counter()
            rec = dep.recover()
            same = same and _same(rec)
            ev = dep.events[-1]
            print(f"[cluster] replayed the pending batch from the fold "
                  f"snapshots in {(time.perf_counter() - t0) * 1e3:.1f}ms: "
                  f"identical={same} replay_from="
                  f"{dict(sorted(ev.replay_from.items()))}", flush=True)
        for b in range(max(args.batches, 1)):
            t0 = time.perf_counter()
            out = dep.run(instances=instances)
            wall = time.perf_counter() - t0
            same = same and all(
                bool((out[k] == seq[k]).all() if hasattr(seq[k], "all")
                     else out[k] == seq[k]) for k in seq)
            if args.batches > 1:
                print(f"[cluster] batch {b} "
                      f"({'cold' if b == 0 else 'warm'}): "
                      f"{wall * 1e3:.1f}ms identical={same}")
        for aev in dep.autoscale_events:
            print(f"[cluster] {aev.describe()}")
        depths = {f"{s}->{d}": n for (s, d), n
                  in dep.transport.channel_depths().items()}
        if args.trace:
            dep.export_trace(args.trace)
            merged = dep.merged_trace()
            print(f"[cluster] trace: {len(merged)} events from "
                  f"{len({e.host for e in merged})} host(s) -> {args.trace}")
            print(dep.metrics().describe())
    print(f"[cluster] {args.transport} over {args.hosts} hosts == "
          f"sequential oracle: {same}")
    print(netlog.cluster_report(plan, out.reports, depths=depths))
    if not same:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
