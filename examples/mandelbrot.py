"""Mandelbrot farm (paper §6.6): row bands fanned over workers, with the
Pallas escape-time kernel as the Worker function.

    PYTHONPATH=src python examples/mandelbrot.py [--width 280]
    PYTHONPATH=src python examples/mandelbrot.py --hosts 2   # cluster mode

``--hosts N`` reruns the paper's capstone: the *same* declarative network is
partitioned over N hosts (real OS processes by default — the
MultiProcessPipe transport) and must produce results bit-identical to the
sequential oracle, with the CSP checker confirming the partitioned network
trace-refines the unpartitioned one.
"""

import argparse

import jax.numpy as jnp
import numpy as np

from repro.core import DataParallelCollect, build, run_sequential
from repro.launch.cache import enable_compile_cache

CHARS = " .:-=+*#%@"


def make_net(width: int, height: int, bands: int, iters: int):
    """Module-level factory: the cluster's pipe transport spawns fresh
    interpreters that rebuild the network from this picklable recipe."""
    from repro.kernels.mandelbrot import ops as mb_ops

    band_h = height // bands
    delta = 3.0 / width

    def create(i):
        """band i: its top row index."""
        return jnp.asarray(i * band_h, jnp.int32)

    def render_band(row0):
        cnt, _ = mb_ops.mandelbrot_band(row0, rows=band_h, width=width,
                                        x0=-2.2, y0=-1.15, pixel_delta=delta,
                                        max_iterations=iters)
        return (row0, cnt)

    def collector(acc, item):
        row0, cnt = item
        acc[int(row0)] = np.asarray(cnt)
        return acc

    return DataParallelCollect(
        create=create, function=render_band, collector=collector, init={},
        workers=bands, name="mandelbrot")


def _assemble(bands: dict) -> np.ndarray:
    return np.concatenate([bands[k] for k in sorted(bands)], axis=0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--width", type=int, default=192)
    ap.add_argument("--height", type=int, default=96)
    ap.add_argument("--bands", type=int, default=8)
    ap.add_argument("--iters", type=int, default=60)
    ap.add_argument("--hosts", type=int, default=0,
                    help="partition the farm over N hosts "
                         "(cluster runtime; 0 = single host)")
    ap.add_argument("--transport", default="pipe",
                    choices=["inprocess", "pipe", "shm", "jaxmesh"],
                    help="cluster channel transport (with --hosts)")
    ap.add_argument("--batches", type=int, default=1,
                    help="batches to stream through ONE warm deployment "
                         "(with --hosts): batch 0 pays spawn+compile, the "
                         "rest run at steady-state speed")
    ap.add_argument("--kill-host", type=int, default=-1, metavar="N",
                    help="elastic-recovery demo (process transports, needs "
                         "--batches >= 3): SIGKILL host N's worker at batch "
                         "2, let the deployment recover (respawn + epoch "
                         "bump + replay of the lost chunks) and print the "
                         "recovery section of the cluster report")
    ap.add_argument("--ascii", action="store_true", default=True)
    args = ap.parse_args()
    enable_compile_cache()

    H, W = args.height, args.width
    factory = (make_net, (W, H, args.bands, args.iters))
    net = make_net(W, H, args.bands, args.iters)

    # sequential oracle — every mode below must match it bit-for-bit
    seq_bands = run_sequential(net, args.bands)["collect"]
    seq_img = _assemble(seq_bands)

    if args.hosts:
        import time

        from repro.cluster import (ClusterDeployment, ClusterError,
                                   check_refinement, partition)
        from repro.core import netlog
        plan = partition(net, hosts=args.hosts)
        print(plan.describe())
        refines = check_refinement(net, plan)
        print(f"partitioned [T= unpartitioned (CSP, both directions): "
              f"{refines}")
        if not refines:
            raise SystemExit(1)
        if args.kill_host >= 0 and args.batches < 3:
            args.batches = 3  # cold batch, warm batch, then the murder
        # one warm deployment serves every batch: spawn + stage compilation
        # are paid exactly once (batch 0), the rest is steady state
        recovered = False
        with ClusterDeployment(net, plan=plan, transport=args.transport,
                               microbatch_size=max(args.bands // 4, 1),
                               factory=factory) as dep:
            for b in range(max(args.batches, 1)):
                if b == 2 and args.kill_host >= 0 and not recovered:
                    print(f"batch {b}: killing host {args.kill_host}'s "
                          "worker process (SIGKILL, mid-deployment)")
                    dep.kill_host(args.kill_host)
                t0 = time.perf_counter()
                try:
                    out = dep.run(instances=args.bands)
                except ClusterError:
                    # the §8 report fired; recover() respawns the corpse,
                    # bumps the plan epoch, re-proves the refinement and
                    # replays exactly the lost chunks of THIS batch
                    t0 = time.perf_counter()
                    out = dep.recover()
                    recovered = True
                    print(f"batch {b}: host failure captured — recovered "
                          f"in {(time.perf_counter() - t0) * 1e3:.1f}ms "
                          f"(epoch {dep.epoch})")
                wall = time.perf_counter() - t0
                img = _assemble(out["collect"])
                same = bool((img == seq_img).all())
                if args.batches > 1:
                    state = "cold" if b == 0 else "warm"
                    print(f"batch {b} ({state}, {wall * 1e3:.1f}ms): "
                          f"identical={same}")
                if not same:
                    break
        print(f"sequential == cluster({args.transport}, {args.hosts} hosts): "
              f"{bool((img == seq_img).all())}")
        print(netlog.cluster_report(dep.plan, out.reports,
                                    events=dep.events))
        if args.kill_host >= 0 and not (recovered and dep.epoch >= 2):
            print("kill-host demo: no recovery happened (host survived?)")
            raise SystemExit(1)
        if not (img == seq_img).all():
            raise SystemExit(1)
    else:
        cn = build(net)
        bands = cn.run(instances=args.bands)["collect"]
        img = _assemble(bands)
        print(f"sequential == parallel: {bool((img == seq_img).all())}")

        # streaming microbatch execution: bands flow through in chunks
        strm_bands = cn.run_streaming(instances=args.bands,
                                      microbatch_size=max(args.bands // 4, 1)
                                      )["collect"]
        strm_img = _assemble(strm_bands)
        print(f"sequential == streaming: {bool((strm_img == seq_img).all())}  "
              f"[{cn.stream_stats.summary()}]")

    if args.ascii:
        step = max(args.iters // (len(CHARS) - 1), 1)
        for r in range(0, H, 2):
            print("".join(CHARS[min(img[r, c] // step, len(CHARS) - 1)]
                          for c in range(W)))

    if not args.hosts:
        # the plain escape-time reference on the full image
        from repro.kernels.mandelbrot import ref as mb_ref
        delta = 3.0 / W
        full = mb_ref.mandelbrot(H, W, x0=-2.2, y0=-1.15, pixel_delta=delta,
                                 max_iterations=args.iters)
        print(f"plain reference == farm image: "
              f"{bool((np.asarray(full) == img).all())}")


if __name__ == "__main__":
    main()
