"""``cluster`` benchmark: single-host streaming vs multi-host cluster runs.

The paper's capstone claim is that the same network runs unchanged on one
machine and on a cluster; this benchmark measures what that portability
costs per transport on the Mandelbrot row-band farm:

* ``single``    — PR 1's streaming executor, one host (the baseline),
* ``inprocess`` — 2-host partition, thread hosts, queue-backed channels,
* ``pipe``      — 2-host partition, *real OS processes* (spawned
                  interpreters; the wall time includes their startup —
                  this is the genuine cross-host cost on CPU),
* ``shm``       — 2-host partition, real OS processes with zero-copy
                  shared-memory ring channels,
* ``jaxmesh``   — 2-host partition over mesh submeshes, channel puts folded
                  into the consumer stage jits.

Each transport gets three rows.  The cold row (``cluster_<t>``) is one
``run_cluster`` call: partition build + host spawn + per-host stage
compilation + one batch — the worst-case deployment cost.  The steady row
(``cluster_<t>_steady``) holds ONE :class:`ClusterDeployment` open, pays
that bill once, then times warm ``deployment.run`` calls — the §7
steady-state story; its ``derived`` string reports the cold/warm split and
the deployed cut-channel capacities so the stall counts are explainable.
The recovery row (``cluster_<t>_recovery``) injects a transient host
failure into a warm deployment and times ``deployment.recover()`` — drain,
epoch bump, §6.1.1 re-proof, replay of the lost chunks — so the elastic
control plane's cost sits next to the warm batch it protects
(``vs_warm`` in the derived string; expected within ~10× of one warm
batch on CPU CI).

Every mode is gated on bit-identical results vs the sequential oracle —
including the recovered batch.

    PYTHONPATH=src python -m benchmarks.cluster --smoke   # BENCH_cluster.json
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

# the launcher's module-level Mandelbrot factory is already picklable (as
# the pipe transport requires) — one definition serves launcher + benchmark
from repro.launch.cluster import make_mandelbrot as make_farm

TRANSPORTS = ("inprocess", "pipe", "shm", "jaxmesh")

# per-process counter behind make_recovery_farm's one-shot failure: spawned
# hosts each import this module fresh, so the trip fires once per deployment
_TRIP = {"n": 0}


def make_recovery_farm(bands: int, height: int, width: int, iters: int,
                       trip_at: int):
    """The Mandelbrot farm with a *transiently* failing host-side collector:
    its ``trip_at``-th item ever (counted per process) raises once, then the
    host is healthy again — the benchmarkable slice of a host failure (a
    SIGKILLed host adds respawn + recompile on top; see the elastic-smoke
    CI step for that path)."""
    import jax.numpy as jnp  # noqa: F401  (keeps parity with make_farm)
    import numpy as np
    from repro.core import DataParallelCollect
    from repro.kernels.mandelbrot import ref

    band_h = height // bands
    delta = 3.0 / width

    def create(i):
        return jnp.asarray(i * band_h, jnp.int32)

    def render(row0):
        return ref.mandelbrot(band_h, width, x0=-2.2,
                              y0=-1.15 + delta * row0, pixel_delta=delta,
                              max_iterations=iters)

    def collector(acc, cnt):
        _TRIP["n"] += 1
        if _TRIP["n"] == trip_at:
            raise RuntimeError("injected transient host failure "
                               f"(item {trip_at})")
        return acc + int(np.sum(np.asarray(cnt)))

    return DataParallelCollect(create=create, function=render,
                               collector=collector, init=0,
                               workers=bands, jit_combine=False,
                               name="mandelbrot-recovery")


def make_manysmall_pipeline(width: int):
    """Many TINY records over one cut channel: the transport-overhead-bound
    regime the coalescing fast path exists for.  ~``4 * width`` bytes per
    record; with ``microbatch_size=1`` every instance is its own record."""
    import jax.numpy as jnp
    from repro.core import OnePipelineCollect
    return OnePipelineCollect(
        create=lambda i: jnp.full((width,), float(i), jnp.float32),
        stage_ops=[lambda x: x * 1.5, lambda x: x + 1.0],
        collector=lambda a, x: a + jnp.sum(x), init=jnp.asarray(0.0),
        jit_combine=True, name="manysmall")


def make_skewed_pipeline(size: int, reps: int):
    """A pipeline whose COST is concentrated in its first two stages while
    its COUNT is uniform: the §6 count-balanced cut piles both heavy stages
    onto host 0, the measured-cost cut splits them 1/1 — the workload where
    ``cost_assignment`` visibly beats ``auto_assignment``."""
    import jax.numpy as jnp
    from repro.core import OnePipelineCollect

    def heavy(x):
        for _ in range(reps):
            x = x @ x
            x = x / jnp.maximum(jnp.max(jnp.abs(x)), 1.0)
        return x

    return OnePipelineCollect(
        create=lambda i: jnp.eye(size, dtype=jnp.float32) * (1.0 + 0.01 * i),
        stage_ops=[heavy, heavy, lambda x: x + 1.0, lambda x: x * 0.5],
        collector=lambda a, x: a + jnp.sum(x), init=jnp.asarray(0.0),
        jit_combine=True, name="skewed")


# run in a FRESH interpreter with XLA_FLAGS set pre-import: jax fixes the
# device count at backend init, so the parent process can't change its own
_VIRTUAL_CODE = """
import json, time
import jax
from repro.core import run_sequential
from repro.cluster import ClusterDeployment
from repro.launch.cluster import make_mandelbrot
fargs = (8, 64, 64, 40)
net = make_mandelbrot(*fargs)
seq = run_sequential(net, fargs[0])["collect"]
with ClusterDeployment(net, hosts=2, transport="jaxmesh",
                       microbatch_size=2,
                       factory=(make_mandelbrot, fargs)) as dep:
    out = dep.run(instances=fargs[0])
    same = bool(out["collect"] == seq)
    warm = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        wout = dep.run(instances=fargs[0])
        warm = min(warm, time.perf_counter() - t0)
        same = same and bool(wout["collect"] == seq)
print(json.dumps({"devices": jax.device_count(), "warm_us": warm * 1e6,
                  "identical": same}))
"""


def _wall(fn, repeats: int = 2) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _stalls(out) -> int:
    return sum(int(r.stats_summary.split("stalls=")[1].split(",")[0])
               for r in out.reports if "stalls=" in r.stats_summary)


def _caps(out) -> str:
    caps: dict = {}
    for r in out.reports:
        caps.update(r.capacities)
    return ",".join(f"{k}={v}" for k, v in sorted(caps.items())) or "none"


def _bytes_rate(out) -> str:
    """Per-channel sender-side bytes/s from the hosts' metrics samples
    (always-on transport byte counters; see PartitionExecutor)."""
    rates: dict = {}
    for r in out.reports:
        m = getattr(r, "metrics", None) or {}
        wall = m.get("wall_s") or 0.0
        for chan, nbytes in (m.get("sent_bytes") or {}).items():
            if wall:
                rates[chan] = nbytes / wall
    return ",".join(f"{k}={v:.0f}B/s"
                    for k, v in sorted(rates.items())) or "none"


def run(*, smoke: bool = False, hosts: int = 2,
        warm_batches: int = 3) -> list:
    from repro.cluster import (ClusterDeployment, ClusterError,
                               check_refinement, partition, run_cluster)
    from repro.core import build, run_sequential

    warm_batches = max(warm_batches, 1)  # the steady row needs >= 1 warm run
    if smoke:
        fargs = (8, 64, 64, 40)
        mb = 2
    else:
        fargs = (16, 256, 256, 100)
        mb = 4
    instances = fargs[0]
    factory = (make_farm, fargs)
    net = factory[0](*fargs)
    plan = partition(net, hosts=hosts)
    refines = check_refinement(net, plan)
    seq = run_sequential(net, instances)["collect"]

    rows = []
    cn = build(net)
    single = _wall(lambda: cn.run_streaming(instances=instances,
                                            microbatch_size=mb))
    same = bool(cn.run_streaming(instances=instances,
                                 microbatch_size=mb)["collect"] == seq)
    rows.append(("cluster_single", single * 1e6,
                 f"identical={same} refines={refines}"))

    for transport in TRANSPORTS:
        # -- cold: one-shot run_cluster (fresh deployment every call) ------
        last = []  # capture inside the timed closure: no extra deployment

        def one(t=transport, last=last):
            last[:] = [run_cluster(net, instances=instances, plan=plan,
                                   transport=t, microbatch_size=mb,
                                   factory=factory)]
        process_hosts = transport in ("pipe", "shm")
        wall = _wall(one, repeats=1 if process_hosts else 2)
        (out,) = last
        same = bool(out["collect"] == seq)
        rows.append((f"cluster_{transport}", wall * 1e6,
                     f"identical={same} hosts={hosts} "
                     f"vs_single={wall / single:.2f}x stalls={_stalls(out)} "
                     f"caps={_caps(out)}"))

        # -- steady: ONE deployment, cold call + warm calls ----------------
        with ClusterDeployment(net, plan=plan, transport=transport,
                               microbatch_size=mb,
                               factory=factory) as dep:
            t0 = time.perf_counter()
            out = dep.run(instances=instances)
            cold = time.perf_counter() - t0
            same = bool(out["collect"] == seq)
            warm = float("inf")
            for _ in range(warm_batches):
                t0 = time.perf_counter()
                wout = dep.run(instances=instances)
                warm = min(warm, time.perf_counter() - t0)
                same = same and bool(wout["collect"] == seq)
            builds = sum(r.jit_builds for r in wout.reports)
        rows.append((f"cluster_{transport}_steady", warm * 1e6,
                     f"identical={same} hosts={hosts} "
                     f"vs_single={warm / single:.2f}x "
                     f"cold_us={cold * 1e6:.0f} warm_us={warm * 1e6:.0f} "
                     f"cold_vs_warm={cold / warm:.1f}x "
                     f"warm_jit_builds={builds} stalls={_stalls(wout)} "
                     f"caps={_caps(wout)} bytes_per_s={_bytes_rate(wout)}"))

        # -- recovery: transient host failure on a warm deployment ---------
        # batch 1 pays the cold bill, batch 2 is the warm reference, batch 3
        # trips the injected failure mid-stream; recover() = drain + epoch
        # bump + §6.1.1 re-proof + replay of the lost chunks
        _TRIP["n"] = 0  # thread transports share this interpreter's counter
        trip_at = instances * 2 + max(instances // 2, 1)
        rfactory = (make_recovery_farm, fargs + (trip_at,))
        rnet = rfactory[0](*rfactory[1])
        with ClusterDeployment(rnet, hosts=hosts, transport=transport,
                               microbatch_size=mb,
                               factory=rfactory) as dep:
            dep.run(instances=instances)
            t0 = time.perf_counter()
            dep.run(instances=instances)
            rwarm = time.perf_counter() - t0
            failed = False
            try:
                dep.run(instances=instances)
            except ClusterError:
                failed = True
            t0 = time.perf_counter()
            rec = dep.recover()
            rwall = time.perf_counter() - t0
            same = failed and bool(int(rec["collect"]) == int(seq))
            (ev,) = dep.events
        rows.append((f"cluster_{transport}_recovery", rwall * 1e6,
                     f"identical={same} hosts={hosts} "
                     f"vs_warm={rwall / rwarm:.2f}x "
                     f"warm_us={rwarm * 1e6:.0f} epoch={rec.epoch} "
                     f"refined={ev.refined} "
                     f"replayed_hosts={len(ev.replay_from)} "
                     f"requeued={sum(len(v) for v in ev.requeued.values())} "
                     f"recovery_jit_builds="
                     f"{sum(r.jit_builds for r in rec.reports)}"))

    # -- durability: what the snapshot stream costs, and what it buys ------
    # overhead row: the same warm deployment with and without fold
    # snapshots.  Snapshots exist for LONG batches (the batches worth
    # replaying from a chunk boundary), so this row measures a ~150ms
    # batch: the per-snapshot cost (drain to a retire-consistent boundary
    # + async Checkpointer write + the controller's write-ahead meta
    # record) is fixed, and the cadence amortises it below 5% (gated via
    # overhead_ok)
    import shutil
    import tempfile

    ofargs = (16, 96, 96, 12000)
    ofactory = (make_farm, ofargs)
    onet = ofactory[0](*ofargs)
    oplan = partition(onet, hosts=hosts)
    oseq = run_sequential(onet, ofargs[0])["collect"]

    def _best_warm(dep) -> tuple:
        dep.run(instances=ofargs[0])  # cold: spawn + compile
        best = float("inf")
        for _ in range(max(warm_batches, 5)):  # relative gate: best-of-5
            t0 = time.perf_counter()
            wout = dep.run(instances=ofargs[0])
            best = min(best, time.perf_counter() - t0)
        return best, wout

    with ClusterDeployment(onet, plan=oplan, transport="inprocess",
                           microbatch_size=mb, factory=ofactory) as dep:
        base, bout = _best_warm(dep)
    sdir = tempfile.mkdtemp(prefix="bench_durable_")
    try:
        with ClusterDeployment(onet, plan=oplan, transport="inprocess",
                               microbatch_size=mb, factory=ofactory,
                               snapshot_every=4, snapshot_dir=sdir) as dep:
            snap, sout = _best_warm(dep)
        same = bool(sout["collect"] == oseq and bout["collect"] == oseq)
        pct = 100.0 * (snap - base) / base
        rows.append(("cluster_inprocess_snapshot_overhead", snap * 1e6,
                     f"identical={same} overhead={pct:+.1f}% "
                     f"overhead_ok={pct <= 5.0} "
                     f"base_us={base * 1e6:.0f} snap_us={snap * 1e6:.0f} "
                     f"snapshot_every=4 hosts={hosts}"))
    finally:
        shutil.rmtree(sdir, ignore_errors=True)

    # replay row: a host failure AFTER a fold snapshot — recover() resumes
    # the stateful host from the snapshot chunk, not chunk 0 (gated via
    # from_snap_ok: the replay must start past chunk 0 and stay identical)
    _TRIP["n"] = 0
    n_chunks = (instances + mb - 1) // mb
    trip_at = instances + instances - mb  # batch 2, last chunk
    rfactory = (make_recovery_farm, fargs + (trip_at,))
    rnet = rfactory[0](*rfactory[1])
    sdir = tempfile.mkdtemp(prefix="bench_replay_")
    try:
        with ClusterDeployment(rnet, hosts=hosts, transport="inprocess",
                               microbatch_size=mb, factory=rfactory,
                               snapshot_every=2, snapshot_dir=sdir) as dep:
            dep.run(instances=instances)
            failed = False
            try:
                dep.run(instances=instances)
            except ClusterError:
                failed = True
            t0 = time.perf_counter()
            rec = dep.recover()
            rwall = time.perf_counter() - t0
            (ev,) = dep.events
            from_chunk = max(ev.replay_from.values(), default=0)
            same = failed and bool(int(rec["collect"]) == int(seq))
        rows.append(("cluster_replay_from_snapshot", rwall * 1e6,
                     f"identical={same} from_chunk={from_chunk} "
                     f"from_snap_ok={from_chunk > 0} "
                     f"chunks={n_chunks} snapshot_every=2 "
                     f"replayed_hosts={len(ev.replay_from)} "
                     f"epoch={rec.epoch} refined={ev.refined}"))
    finally:
        shutil.rmtree(sdir, ignore_errors=True)

    # -- transport fast path: coalesced small records vs per-record shm ----
    # many tiny records over one cut channel; the coalesced deployment
    # packs them ~8/slot (one ring slot + one header per flush instead of
    # per record) and must be at least as fast, still bit-identical
    cw, cn_inst, cmb, cbudget = 256, 48, 1, 1 << 13
    cfactory = (make_manysmall_pipeline, (cw,))
    cnet = cfactory[0](*cfactory[1])
    cseq = run_sequential(cnet, cn_inst)["collect"]

    def _steady_shm(coalesce: int) -> tuple:
        with ClusterDeployment(cnet, hosts=hosts, transport="shm",
                               microbatch_size=cmb, factory=cfactory,
                               coalesce_bytes=coalesce) as dep:
            out = dep.run(instances=cn_inst)
            same = bool(abs(float(out["collect"]) - float(cseq)) == 0.0)
            warm = float("inf")
            # best-of-5: these rows gate a RELATIVE timing claim, so buy
            # extra samples against scheduler noise
            for _ in range(max(warm_batches, 5)):
                t0 = time.perf_counter()
                wout = dep.run(instances=cn_inst)
                warm = min(warm, time.perf_counter() - t0)
                same = same and bool(
                    abs(float(wout["collect"]) - float(cseq)) == 0.0)
        return warm, same

    base_warm, base_same = _steady_shm(0)
    coal_warm, coal_same = _steady_shm(cbudget)
    # allow 5% timing noise on CI: the fast path must never LOSE, the
    # usual win on this record mix is well past the tolerance
    coalesce_ok = coal_warm <= base_warm * 1.05
    rows.append(("cluster_shm_coalesce_steady", coal_warm * 1e6,
                 f"identical={base_same and coal_same} "
                 f"coalesce_ok={coalesce_ok} "
                 f"speedup={base_warm / coal_warm:.2f}x "
                 f"base_us={base_warm * 1e6:.0f} "
                 f"coalesce_bytes={cbudget} records={cn_inst} "
                 f"record_bytes={4 * cw} hosts={hosts}"))

    # -- measured-cost cut vs count cut on a cost-skewed pipeline ----------
    # (128, 24) puts ~2ms of matmul per record in EACH heavy stage, so the
    # count cut's doubled-up host carries ~4ms/chunk more than the cost
    # cut's bottleneck — far past scheduler noise on a busy CI box
    from repro.cluster import calibrate, cost_assignment
    sfactory = (make_skewed_pipeline, (128, 24))
    snet = sfactory[0](*sfactory[1])
    s_inst, smb = 8, 2
    sseq = run_sequential(snet, s_inst)["collect"]
    t0 = time.perf_counter()
    profile = calibrate(snet, instances=s_inst, microbatch_size=smb,
                        transports=("inprocess",))
    calib_s = time.perf_counter() - t0
    count_plan = partition(snet, hosts=hosts)
    cost_plan = partition(snet, assignment=cost_assignment(
        snet, hosts, profile, transport="inprocess"))
    refined = (check_refinement(snet, cost_plan)
               and check_refinement(snet, count_plan))

    def _steady_plan(plan) -> tuple:
        with ClusterDeployment(snet, plan=plan, transport="inprocess",
                               microbatch_size=smb, factory=sfactory,
                               profile=profile) as dep:
            out = dep.run(instances=s_inst)
            same = bool(float(out["collect"]) == float(sseq))
            warm = float("inf")
            for _ in range(max(warm_batches, 5)):  # relative gate: best-of-5
                t0 = time.perf_counter()
                wout = dep.run(instances=s_inst)
                warm = min(warm, time.perf_counter() - t0)
                same = same and bool(float(wout["collect"]) == float(sseq))
        return warm, same

    count_warm, count_same = _steady_plan(count_plan)
    cost_warm, cost_same = _steady_plan(cost_plan)
    cost_ok = cost_warm <= count_warm * 1.05
    rows.append(("cluster_cost_cut_steady", cost_warm * 1e6,
                 f"identical={count_same and cost_same} cost_ok={cost_ok} "
                 f"refined={refined} speedup={count_warm / cost_warm:.2f}x "
                 f"count_us={count_warm * 1e6:.0f} "
                 f"calib_ms={calib_s * 1e3:.0f} hosts={hosts}"))
    with open("BENCH_costs.json", "w") as f:
        json.dump({
            "benchmark": "costs",
            "profile": profile.to_json(),
            "calibrate_ms": calib_s * 1e3,
            "cost_us": cost_warm * 1e6, "count_us": count_warm * 1e6,
            "cost_assignment": dict(cost_plan.assignment),
            "count_assignment": dict(count_plan.assignment),
            "refined": bool(refined),
        }, f, indent=2)

    # -- load-driven autoscaling: spike -> scale-out (ROADMAP item 1) ------
    # warm 2-host baseline sets the latency SLO; a 4x traffic spike
    # crosses it, the policy adds a host (epoch bump + re-proof, never a
    # restart), and post-scale-up throughput must at least hold the
    # pre-spike baseline — the acceptance gate for the autoscaler
    from repro.cluster import AutoscalePolicy
    a_inst, a_mult, amb = 6, 4, 2
    afactory = (make_skewed_pipeline, (96, 12))
    anet = afactory[0](*afactory[1])
    aseq = float(run_sequential(anet, a_inst)["collect"])
    aseq_big = float(run_sequential(anet, a_inst * a_mult)["collect"])
    apolicy = AutoscalePolicy(high_occupancy=2.0, high_stall_rate=1e9,
                              sustain=1, cooldown=1,
                              min_hosts=hosts, max_hosts=hosts + 1)
    with ClusterDeployment(anet, hosts=hosts, transport="inprocess",
                           microbatch_size=amb, factory=afactory,
                           autoscale=apolicy) as adep:
        adep.run(instances=a_inst)  # cold: spawn + compile
        a_base, a_same = float("inf"), True
        for _ in range(max(warm_batches, 3)):
            t0 = time.perf_counter()
            out = adep.run(instances=a_inst)
            a_base = min(a_base, time.perf_counter() - t0)
            a_same = a_same and float(out["collect"]) == aseq
        base_tps = a_inst / a_base
        apolicy.high_batch_wall_s = 2.0 * a_base  # the SLO the spike crosses
        spike_walls = []
        for _ in range(max(warm_batches, 3) + 1):
            t0 = time.perf_counter()
            out = adep.run(instances=a_inst * a_mult)
            spike_walls.append(time.perf_counter() - t0)
            a_same = a_same and float(out["collect"]) == aseq_big
        scaled = [e for e in adep.autoscale_events if e.executed]
        a_refined = all(e.event.refined is True for e in scaled)
        a_hosts = len(adep.controller.plan.hosts())
    post = min(spike_walls[1:])  # batches after the scale-out landed
    post_tps = a_inst * a_mult / post
    scaleup_ok = bool(scaled) and a_hosts == hosts + 1 \
        and post_tps >= base_tps
    rows.append(("cluster_autoscale_spike", post * 1e6,
                 f"identical={a_same} scaleup_ok={scaleup_ok} "
                 f"refined={a_refined} actions={len(scaled)} "
                 f"post_tps={post_tps:.1f} base_tps={base_tps:.1f} "
                 f"spike0_us={spike_walls[0] * 1e6:.0f} "
                 f"hosts={hosts}->{a_hosts}"))

    # -- jaxmesh over virtual devices (satellite: --virtual-devices) -------
    # fresh interpreters: XLA fixes the device count at backend init.  The
    # virtual devices are CPU devices, so the child is pinned to the CPU: it
    # must never contend for an accelerator this process may hold
    for n in (4, 8):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}").strip()
        env.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
        proc = subprocess.run([sys.executable, "-c", _VIRTUAL_CODE],
                              env=env, capture_output=True, text=True,
                              timeout=600)
        if proc.returncode != 0:
            raise RuntimeError(
                f"cluster_jaxmesh_virtual{n} subprocess failed:\n"
                + proc.stderr[-2000:])
        info = json.loads(proc.stdout.strip().splitlines()[-1])
        rows.append((f"cluster_jaxmesh_virtual{n}", info["warm_us"],
                     f"identical={info['identical']} "
                     f"devices={info['devices']} "
                     f"devices_ok={info['devices'] == n} hosts=2"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--hosts", type=int, default=2)
    ap.add_argument("--warm-batches", type=int, default=3)
    args = ap.parse_args()
    rows = run(smoke=args.smoke, hosts=args.hosts,
               warm_batches=args.warm_batches)
    print("name,us_per_call,derived")
    blob = []
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")
        blob.append({"name": name, "us_per_call": us, "derived": derived})
    bad = ("identical=False", "refines=False", "overhead_ok=False",
           "from_snap_ok=False", "coalesce_ok=False", "cost_ok=False",
           "refined=False", "devices_ok=False", "scaleup_ok=False")
    if any(b in r["derived"] for r in blob for b in bad):
        print("cluster benchmark: oracle divergence, refinement failure, "
              "or durability gate miss", file=sys.stderr)
        sys.exit(1)
    with open("BENCH_cluster.json", "w") as f:
        json.dump({"benchmark": "cluster",
                   "mode": "smoke" if args.smoke else "full",
                   "hosts": args.hosts, "rows": blob}, f, indent=2)
    print("wrote BENCH_cluster.json", file=sys.stderr)


if __name__ == "__main__":
    main()
