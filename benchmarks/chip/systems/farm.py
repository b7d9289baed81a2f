"""Process-network cells: the Mandelbrot farm deployed over chips.

The program's network (``examples.mandelbrot.make_net``) is partitioned as
the configuration says and run as a ``ClusterDeployment`` over the
``jaxmesh`` transport, one host per chip.  A closed loop: one caller runs a
batch of every band, waits for the whole image, and runs the next.  Batch
``k`` renders the image at a vertical offset drawn from the seed, so every
batch's answer differs; the work per batch is fixed.  The collector counts
what it is handed, so after the window each batch is checked for every band
delivered exactly once, and a sample of batches drawn from the seed (and
the last) is compared pixel by pixel with the plain reference.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from harness import common, tracing

SAMPLE_FROM = 64          # the sample is drawn from the first batches
WARM = 1 << 20            # batch numbers of the warm-up, never timed


def run(r) -> None:
    import jax
    import jax.numpy as jnp
    from examples.mandelbrot import make_net
    from repro.cluster import ClusterDeployment, partition
    from repro.cluster.transport import JaxMesh

    cfg = r.cfg
    W, H, B, I = (int(cfg[k]) for k in ("width", "height", "bands", "iters"))
    band_h = H // B
    devs = jax.devices()[:r.chips]
    net = make_net(W, H, B, I)
    plan = partition(net, assignment=dict(cfg["assignment"]))
    dep = ClusterDeployment(net, plan=plan, transport=JaxMesh(devices=devs),
                            microbatch_size=int(cfg["microbatch_size"]),
                            trace=r.trace)
    # the collector keeps bands in a dict, which would hide a band handed
    # to it twice: count every item its fold is handed
    delivered = [0]
    collect = dep.net.procs["collect"]

    def counted(acc, item, fold=collect.fn):
        delivered[0] += 1
        return fold(acc, item)

    dep.net.procs["collect"] = dataclasses.replace(collect, fn=counted)
    if r.fault is not None:
        r.fault(dep)
    dep.start()
    rng = np.random.default_rng(np.random.SeedSequence([int(r.seed),
                                                        0xFA4A]))
    k_sample = int(r.mix.get("sample", 8))
    sample = set(rng.choice(SAMPLE_FROM, size=k_sample, replace=False)
                 .tolist())

    def top(k: int) -> int:
        """Batch k's top row: the image shifted by up to a quarter of its
        height, from the seed."""
        return int(np.random.default_rng(
            np.random.SeedSequence([int(r.seed), 0xFA4A, k])).integers(
                -(H // 4), H // 4 + 1))

    def rows(k: int):
        return jnp.asarray(top(k) + np.arange(B) * band_h, jnp.int32)

    for k in (WARM, WARM + 1):               # warm-up: compile, then warm
        dep.run(batch=rows(k))

    kept, misdelivered, done = {}, 0, []
    last = None

    def one(k: int) -> None:
        """Batch k; a band missing, unasked for, of the wrong shape or
        handed to the collector more than once counts as misdelivered."""
        nonlocal misdelivered, last
        n0 = delivered[0]
        out = dep.run(batch=rows(k))["collect"]
        want = {top(k) + b * band_h for b in range(B)}
        got = {int(x) for x in out}
        misdelivered += len(want ^ got) + (delivered[0] - n0 - len(got)) + \
            sum(1 for v in out.values() if np.shape(v) != (band_h, W))
        if k in sample:
            kept[k] = out
        last = (k, out)
        done.append(time.perf_counter())

    T = float(r.seconds)
    r.setup_s = time.perf_counter() - r.clock.t0
    common.log(f"[setup] {r.setup_s:.2f} s; " + r.compiles.since((0, 0, 0.0)))
    in_window = r.compiles.snapshot()
    w0 = time.perf_counter()
    k = 0
    if r.trace:
        trace_s = min(T, float(r.mix.get("trace_s", 10.0)))
        dep.clear_trace()
        with tracing.capture(r.log_dir) as host_start:
            tw0 = time.perf_counter()
            while time.perf_counter() < tw0 + trace_s:
                one(k)
                k += 1
            tw1 = time.perf_counter()
        traced_batches = k
        spans = dep.merged_trace()
    while time.perf_counter() < w0 + T:
        one(k)
        k += 1
    common.log("[window] " + r.compiles.since(in_window))
    r.data["batches"] = k
    r.data["window_s"] = done[-1] - w0
    r.memory_peak = common.memory_peak(devs)
    if last[0] not in kept:
        kept[last[0]] = last[1]
    worker = int(cfg["assignment"]["group"])
    if r.trace:
        r.data["trace_window"] = (tw0, tw1)
        r.data["traced_batches"] = traced_batches
        r.data["worker_device"] = worker
        r.data["host_spans"] = [
            (e.host, e.name, e.ts, e.ts + e.dur) for e in spans
            if e.kind == "span"]
        r.trace_data = tracing.load(r.log_dir, host_start)
        r.devices_used = list(range(r.chips))
    dep.close()
    del dep
    common.log(f"[farm] {k} batches in {r.data['window_s']:.3f}s; "
               f"checking {len(kept)} against the reference")

    ref = common.load_module("references", cfg["family"])
    delta = float(cfg["span"]) / W
    bad = 0
    for kk in sorted(kept):
        out = kept[kk]
        tops = sorted(out)
        got = np.concatenate([np.asarray(out[t]) for t in tops])
        want_rows = np.concatenate([t + np.arange(band_h) for t in tops])
        want = np.asarray(ref.counts(jnp.asarray(want_rows, jnp.int32), W,
                                     float(cfg["x0"]), float(cfg["y0"]),
                                     delta, I))
        bad += int(np.sum(got != want)) if got.shape == want.shape \
            else got.size + want.size
    r.attempted = k
    r.failed = misdelivered
    for name, value in (("misdelivered_bands", misdelivered),
                        ("mismatched_pixels", bad)):
        r.checks.append((name, value, r.limits[name]["limit"]))
    r.correct = all(v <= lim for _, v, lim in r.checks)
    common.log(f"[check] {len(kept)} batches of {B * band_h * W} pixels: "
               f"{bad} differ from the reference; {misdelivered} bands not "
               f"delivered exactly once")
