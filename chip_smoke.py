"""Smoke run of the main path on the TPU: kernels, serving, the Mandelbrot farm.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the jaxmesh farm only

Everything runs in this one process, which holds the chip(s) throughout.

* device  -- fails unless JAX's first device is a TPU; there is no fallback.
* kernels -- each Pallas kernel compiled by Mosaic and run at real widths,
  checked against its ``ref.py`` at highest matmul precision.
* serving -- qwen2-0.5b at full published width through ``ServeEngine`` +
  ``LocalDecodeBackend``: every request is answered; each token stream
  equals the same request served alone in the same engine; each request's
  first-step logits are within ``LOGITS_REL_TOL`` of a float32
  ``Model.forward`` at highest matmul precision; and each stream equals the
  sequential oracle (one request at a time in a 1-slot engine) up to where
  the float32 reference's top two tokens are a near-tie.
* farm    -- the paper's Mandelbrot farm (``examples/mandelbrot.make_net``):
  ``build(net).run`` and ``run_streaming`` equal ``run_sequential``.
* mesh (``--chips 4`` only) -- the same farm deployed with the ``jaxmesh``
  transport, four hosts with one host per chip, bit-identical to the farm
  on one chip, with the device that held each host's values printed.

Every check prints a line; any failure raises, so the exit code is non-zero
and the result line is never printed.  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Times printed are one-off readings of this run, compilation included where
marked; they are not benchmark results.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

# serving workload (seeded): prompts of PROMPT_LEN tokens, MAX_NEW new each
N_REQUESTS, N_SLOTS, MAX_LEN, MAX_NEW = 16, 8, 1024, 32
PROMPT_LEN = (32, 256)
# the served path computes in bfloat16 (8 significand bits) through 24
# layers; the reference is float32 at highest precision.  Relative L2 error
# of a request's first-step logits row, fixed before the first chip run.
LOGITS_REL_TOL = 5e-2
# the farm: 4096 x 4096 pixels in 64 row bands, 1000 escape iterations
FARM = dict(width=4096, height=4096, bands=64, iters=1000)


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    print(f"  {'ok  ' if ok else 'FAIL'} {what}", flush=True)
    if not ok:
        raise SmokeFailure(what)


def _timed(fn):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


# --------------------------------------------------------------------------
# device
# --------------------------------------------------------------------------

def device_phase(chips: int) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    print(f"[device] {info}", flush=True)
    if d.platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX's first device is {d!r}")
    if len(devs) < chips:
        raise SmokeFailure(f"--chips {chips} but JAX sees {len(devs)}")
    return info


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------

def kernel_phase(seed: int, *, flash=(1, 14, 2, 2048, 64),
                 gmm=(2048, 2048, 1408, 64), ssd=(1, 2048, 80, 64, 128, 64),
                 stencil=(8192, 8192), mandel=(4096, 4096, 1000)) -> None:
    """Each kernel at the widths of the configs it serves: flash at
    qwen2-0.5b prefill (B, H, K, S, hd); gmm at deepseek-moe-16b's
    (tokens, d_model, d_expert, experts); ssd at mamba2-2.7b's
    (batch, S, heads, head_dim, d_state, chunk).  Tolerances are the repo's
    own (tests/test_kernels.py) for the dtype."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import use_interpreter
    from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
    from repro.kernels.mandelbrot import ops as mb_ops, ref as mb_ref
    from repro.kernels.moe_gmm import ops as gmm_ops, ref as gmm_ref
    from repro.kernels.ssd_scan import ops as ssd_ops, ref as ssd_ref
    from repro.kernels.stencil import ops as st_ops, ref as st_ref

    print("[kernels]", flush=True)
    check(not use_interpreter(), "kernels compile with Mosaic "
          "(not the interpreter)")
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    normal = lambda shape, dt=jnp.float32: jax.random.normal(
        next(keys), shape, jnp.float32).astype(dt)
    highest = jax.default_matmul_precision("highest")
    failures = []

    def report(name, out, ref, secs, tol):
        out = np.asarray(out, np.float32)
        ref = np.asarray(ref, np.float32)
        err = float(np.max(np.abs(out - ref)))
        bound = tol * (1.0 + np.abs(ref))  # atol = rtol = tol, allclose-style
        ok = bool(np.all(np.abs(out - ref) <= bound))
        print(f"  {name}: max_abs_err={err!r} tol={tol} "
              f"max_abs_ref={float(np.max(np.abs(ref)))!r} "
              f"first_call_s={secs[0]:.3f} warm_call_s={secs[1]:.4f} "
              f"{'ok' if ok else 'FAIL'}", flush=True)
        if not ok:
            failures.append(name)

    def run_twice(fn):
        out, cold = _timed(fn)
        _, warm = _timed(fn)
        return out, (cold, warm)

    # flash attention, bf16 (the model's compute dtype)
    B, H, K, S, hd = flash
    q, k, v = (normal((B, H, S, hd), jnp.bfloat16),
               normal((B, K, S, hd), jnp.bfloat16),
               normal((B, K, S, hd), jnp.bfloat16))
    out, secs = run_twice(lambda: fa_ops.mha(q, k, v, causal=True))
    with highest:
        ref = jax.jit(fa_ref.mha)(*(a.astype(jnp.float32) for a in (q, k, v)))
    report(f"flash_attention B{B} H{H} K{K} S{S} hd{hd} bf16", out, ref,
           secs, 5e-2)

    # grouped expert matmul, bf16; the oracle gathers a (T, D, F) weight
    # block, so it runs over 128-token slices
    T, D, F, E = gmm
    x = normal((T, D), jnp.bfloat16)
    w = (normal((E, D, F)) * D ** -0.5).astype(jnp.bfloat16)
    eo = jax.random.randint(next(keys), (T,), 0, E)
    out, secs = run_twice(lambda: gmm_ops.moe_apply(x, eo, w))
    with highest:
        g = jax.jit(gmm_ref.gmm)
        ref = jnp.concatenate([g(x[i:i + 128], eo[i:i + 128], w)
                               for i in range(0, T, 128)])
    report(f"moe_gmm T{T} D{D} F{F} E{E} bf16", out, ref, secs, 5e-2)

    # SSD chunked scan, f32, against the per-step recurrence
    b, S, Hs, P, N, chunk = ssd
    xs = normal((b, S, Hs, P))
    dt = jax.random.uniform(next(keys), (b, S, Hs)) * 0.1
    A = -jax.random.uniform(next(keys), (Hs,)) - 0.1
    Bm, Cm = normal((b, S, 1, N)) * 0.3, normal((b, S, 1, N)) * 0.3
    out, secs = run_twice(lambda: ssd_ops.ssd(xs, dt, A, Bm, Cm, chunk=chunk,
                                              use_pallas=True))
    fold = lambda a: jnp.moveaxis(a, 2, 1).reshape((b * Hs, S) + a.shape[3:])
    # the recurrence runs on the host CPU: on a v5e, XLA's f32 exp is
    # ~3.5e-6 off and 2048 steps compound it to ~1e-4 (measured), while the
    # CPU's stays ~1e-6 from a float64 recurrence
    cpu = jax.devices("cpu")[0]
    y, _ = jax.jit(ssd_ref.ssd_naive)(*jax.device_put(
        (fold(xs), fold(dt), jnp.tile(A, b),
         fold(jnp.broadcast_to(Bm, (b, S, Hs, N))),
         fold(jnp.broadcast_to(Cm, (b, S, Hs, N)))), cpu))
    ref = jnp.moveaxis(y.reshape(b, Hs, S, P), 1, 2)
    report(f"ssd_scan b{b} S{S} H{Hs} P{P} N{N} chunk{chunk} f32", out, ref,
           secs, 1e-4)

    # 2-D stencil, f32
    Hh, W = stencil
    img = normal((Hh, W))
    taps = normal((3, 3))
    out, secs = run_twice(lambda: st_ops.stencil2d(img, taps))
    ref = jax.jit(st_ref.stencil2d)(img, taps)
    report(f"stencil {Hh}x{W} 3x3 f32 tile_h={st_ops.tile_rows(Hh, W, 4)}",
           out, ref, secs, 1e-4)

    # Mandelbrot: escape counts; boundary pixels may differ by an ulp's
    # worth of contraction, so the repo's criterion is a share of pixels
    Hm, Wm, iters = mandel
    kw = dict(x0=-2.2, y0=-1.15, pixel_delta=3.0 / Wm, max_iterations=iters)
    out, secs = run_twice(lambda: mb_ops.mandelbrot(Hm, Wm, **kw))
    ref = jax.jit(lambda: mb_ref.mandelbrot(Hm, Wm, **kw))()
    diff = np.asarray(out) != np.asarray(ref)
    ok = float(diff.mean()) < 1e-3
    print(f"  mandelbrot {Hm}x{Wm} iters={iters}: differing_pixels="
          f"{int(diff.sum())} of {diff.size} "
          f"max_count_err={int(np.max(np.abs(np.asarray(out) - np.asarray(ref))))} "
          f"first_call_s={secs[0]:.3f} warm_call_s={secs[1]:.4f} "
          f"{'ok' if ok else 'FAIL'}", flush=True)
    if not ok:
        failures.append("mandelbrot")
    check(not failures, f"every kernel within tolerance (failed: {failures})")


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------

def requests(seed, vocab, n, prompt_len, max_new) -> list:
    """``n`` seeded requests, prompts of uniform length in ``prompt_len``."""
    import numpy as np

    from repro.serve import Request
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=tuple(int(t) for t in rng.integers(
                0, vocab, int(rng.integers(prompt_len[0], prompt_len[1] + 1)))),
                    max_new=max_new)
            for i in range(n)]


def serving_phase(seed: int, cfg=None, *, n_requests=N_REQUESTS,
                  n_slots=N_SLOTS, max_len=MAX_LEN, max_new=MAX_NEW,
                  prompt_len=PROMPT_LEN) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models import Model
    from repro.serve import LocalDecodeBackend, ServeEngine

    cfg = get_config("qwen2-0.5b") if cfg is None else cfg
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    print(f"[serving] {cfg.name} d_model={cfg.d_model} layers={cfg.n_layers} "
          f"vocab={cfg.vocab} params={model.param_count(params)} "
          f"compute={cfg.compute_dtype}", flush=True)
    reqs = requests(seed, cfg.vocab, n_requests, prompt_len, max_new)

    class FirstStepLogits(LocalDecodeBackend):
        """The local backend, also keeping each admitted request's logits
        at its first decode step (requests are admitted in rid order)."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.first: list = []
            self._fresh: list = []
            self._logits = jax.jit(lambda p, c, t, adv: self.model.decode_step(
                p, c, t[:, None], advance=adv)[0][:, -1])

        def reset(self, slot):
            super().reset(slot)
            self._fresh.append(slot)

        def decode(self, last, adv):
            if self._fresh:
                lg = self._logits(self.params, self.cache, jnp.asarray(last),
                                  jnp.asarray(adv))
                self.first += [np.asarray(lg[s], np.float32)
                               for s in self._fresh]
                self._fresh = []
            return super().decode(last, adv)

    def serve(slots, one_at_a_time=False):
        """Token streams of ``reqs`` and the backend that served them."""
        t0 = time.perf_counter()
        backend = FirstStepLogits(model, params, n_slots=slots,
                                  max_len=max_len)
        with ServeEngine(backend) as eng:
            for r in reqs:
                eng.submit(r)
                if one_at_a_time:
                    eng.run_until_drained()
            eng.run_until_drained()
            got = {r.rid: eng.poll(r.rid) for r in reqs}
        print(f"  {slots} slot(s){', one request at a time' * one_at_a_time}"
              f": {eng.steps_run} decode steps, "
              f"{time.perf_counter() - t0:.1f}s incl. compile", flush=True)
        return got, backend

    def first_divergence(a, b) -> dict:
        return {rid: next(i for i, (x, y) in enumerate(zip(a[rid], b[rid]))
                          if x != y)
                for rid in a if a[rid] != b[rid]}

    print(f"  {n_requests} requests, "
          f"{sum(len(r.prompt) for r in reqs)} prompt tokens", flush=True)
    got, backend = serve(n_slots)
    check(all(g is not None and len(g.tokens) == max_new
              for g in got.values()),
          f"all {n_requests} requests answered with {max_new} tokens")
    got = {rid: g.tokens for rid, g in got.items()}

    # batching neighbours: the same requests one at a time through the same
    # engine run the same compiled step, row for row
    alone, _ = serve(n_slots, one_at_a_time=True)
    check(got == {rid: g.tokens for rid, g in alone.items()},
          f"every stream bit-identical to the same request served alone in "
          f"the {n_slots}-slot engine")

    # the sequential oracle: one request at a time in a 1-slot engine
    oracle, oracle_backend = serve(1)
    want = {rid: g.tokens for rid, g in oracle.items()}
    differ = first_divergence(got, want)
    print(f"  {n_requests - len(differ)}/{n_requests} token streams "
          f"bit-identical to the 1-slot oracle", flush=True)
    if differ:  # judged after the logits check below
        print(f"  token streams differ (rid: first differing position): "
              f"{differ}", flush=True)

    # the float32 reference at highest precision: logits at the last
    # position of each token sequence, padded to one shape (causal: padding
    # after a sequence cannot reach its logits)
    ref_model = Model(dataclasses.replace(cfg, compute_dtype="float32"))
    L = prompt_len[1] + max_new

    @jax.jit
    def last_logits(p, toks, last):
        logits, _ = ref_model.forward(p, toks)
        return logits[jnp.arange(toks.shape[0]), last]

    def reference(seqs) -> np.ndarray:
        rows = []
        with jax.default_matmul_precision("highest"):
            for lo in range(0, len(seqs), n_slots):
                batch = seqs[lo:lo + n_slots]
                toks = np.zeros((n_slots, L), np.int32)
                for j, seq in enumerate(batch):
                    toks[j, :len(seq)] = seq
                last = [len(seq) - 1 for seq in batch]
                last += [0] * (n_slots - len(batch))
                rows.append(np.asarray(last_logits(
                    params, jnp.asarray(toks), jnp.asarray(last)),
                    np.float32)[:len(batch)])
        return np.concatenate(rows)

    # first-step logits of both engines against the reference
    ref = reference([r.prompt for r in reqs])
    abs_err = {}
    for slots, b in ((n_slots, backend), (1, oracle_backend)):
        check(len(b.first) == n_requests,
              f"{slots}-slot first-step logits captured for all "
              f"{n_requests} requests")
        first = np.stack(b.first)
        errs = np.linalg.norm(first - ref, axis=1) / np.linalg.norm(ref,
                                                                    axis=1)
        abs_err[slots] = float(np.max(np.abs(first - ref)))
        top1 = int(np.sum(np.argmax(first, 1) == np.argmax(ref, 1)))
        print(f"  {slots}-slot first-step logits vs float32 forward (highest "
              f"precision): rel_l2_err max={float(errs.max())!r} "
              f"mean={float(errs.mean())!r} tol={LOGITS_REL_TOL}; "
              f"max_abs_err={abs_err[slots]!r}; top-1 agrees on "
              f"{top1}/{n_requests}", flush=True)
        check(float(errs.max()) <= LOGITS_REL_TOL,
              f"{slots}-slot first-step logits within rel L2 "
              f"{LOGITS_REL_TOL} of the reference")

    # On a TPU the 8-slot and 1-slot decode steps are different programs:
    # XLA emits a batch-1 projection as a VPU multiply-reduce and a batch-8
    # one on the MXU, which sum in another order, so greedy streams can part
    # at a near-tie.  Each engine's logits are within abs_err of the
    # reference, so they can pick different tokens only where the
    # reference's two candidates are within 2 * max(abs_err): that is the
    # check.
    if differ:
        rids = sorted(differ)
        ref = reference([reqs[rid].prompt + want[rid][:differ[rid]]
                         for rid in rids])
        gaps = {rid: float(abs(ref[j][got[rid][differ[rid]]]
                               - ref[j][want[rid][differ[rid]]]))
                for j, rid in enumerate(rids)}
        bound = 2 * max(abs_err.values())
        print(f"  at each divergence, |reference logit gap| of the two "
              f"tokens (rid: gap): {gaps}; bound 2*max_abs_err={bound!r}",
              flush=True)
        check(max(gaps.values()) <= bound,
              "every divergence from the 1-slot oracle starts at a near-tie "
              "of the float32 reference")


# --------------------------------------------------------------------------
# the Mandelbrot farm
# --------------------------------------------------------------------------

def farm_phase(*, width, height, bands, iters) -> None:
    import numpy as np

    from examples.mandelbrot import _assemble, make_net
    from repro.core import build, run_sequential
    print(f"[farm] mandelbrot {width}x{height}, {bands} bands, "
          f"{iters} iterations", flush=True)
    net = make_net(width, height, bands, iters)
    (seq, secs) = _timed(lambda: run_sequential(net, bands)["collect"])
    seq = _assemble(seq)
    print(f"  run_sequential: {secs:.1f}s incl. compile", flush=True)
    cn = build(net)
    fused, secs = _timed(lambda: cn.run(instances=bands)["collect"])
    print(f"  build(net).run: {secs:.2f}s incl. compile", flush=True)
    strm, secs = _timed(lambda: cn.run_streaming(
        instances=bands, microbatch_size=max(bands // 4, 1))["collect"])
    print(f"  run_streaming: {secs:.2f}s incl. compile "
          f"[{cn.stream_stats.summary()}]", flush=True)
    check(bool(np.array_equal(_assemble(fused), seq)),
          "build(net).run == run_sequential (bit-identical)")
    check(bool(np.array_equal(_assemble(strm), seq)),
          "run_streaming == run_sequential (bit-identical)")


def mesh_phase(*, width, height, bands, iters) -> None:
    """The farm over four jaxmesh hosts, one per chip: emit | spreader |
    workers | merge + collect.  (``partition(hosts=4)`` would leave one of
    the four hosts empty for this five-process chain.)"""
    import jax
    import numpy as np

    from repro.cluster import ClusterDeployment, partition
    from repro.cluster.transport import JaxMesh
    from examples.mandelbrot import _assemble, make_net
    from repro.core import build

    devs = jax.devices()[:4]
    net = make_net(width, height, bands, iters)
    plan = partition(net, assignment={"emit": 0, "ofa": 1, "group": 2,
                                      "afo": 3, "collect": 3})
    print(f"[mesh] mandelbrot {width}x{height}, {bands} bands, {iters} "
          f"iterations over {plan.n_hosts} jaxmesh hosts on "
          f"{[d.id for d in devs]}", flush=True)

    class PlacementProbe(JaxMesh):
        """JaxMesh noting the devices that hold what each host sends, and
        where a value the transport places for its consumer lands."""

        def __init__(self):
            super().__init__(devices=devs)
            self.sent: dict = {}
            self.landed: dict = {}

        @staticmethod
        def _note(table, host, value):
            for leaf in jax.tree_util.tree_leaves(value):
                if isinstance(leaf, jax.Array):
                    table.setdefault(host, set()).update(
                        d.id for d in leaf.devices())

        def _send_transform(self, chan, value):
            self._note(self.sent, plan.assignment[chan[0]], value)
            out = super()._send_transform(chan, value)
            if out is not value:
                self._note(self.landed, plan.assignment[chan[1]], out)
            return out

    one, secs = _timed(lambda: build(net).run(instances=bands)["collect"])
    one = _assemble(one)
    print(f"  one chip (build(net).run on {devs[0].id}): {secs:.2f}s "
          f"incl. compile", flush=True)
    probe = PlacementProbe()
    with ClusterDeployment(net, plan=plan, transport=probe,
                           microbatch_size=max(bands // 4, 1)) as dep:
        out, secs = _timed(lambda: dep.run(instances=bands))
        _, warm = _timed(lambda: dep.run(instances=bands))
    print(f"  jaxmesh, 4 hosts: cold {secs:.2f}s incl. compile, "
          f"warm {warm:.3f}s", flush=True)
    for h in range(plan.n_hosts):
        print(f"  host {h} ({', '.join(plan.procs_of(h))}): sent values "
              f"held on devices {sorted(probe.sent.get(h, ()))}; values "
              f"placed for it on {sorted(probe.landed.get(h, ()))}",
              flush=True)
    check(bool(np.array_equal(_assemble(out["collect"]), one)),
          "jaxmesh farm over 4 chips == the farm on one chip (bit-identical)")
    want_sent = {h: {devs[h].id} for h in range(3)}
    check({h: probe.sent.get(h) for h in range(3)} == want_sent,
          f"hosts 0-2 computed on their own chips {want_sent}")
    check(probe.landed.get(3) == {devs[3].id},
          f"host 3's inputs were placed on chip {devs[3].id}")


# --------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip jaxmesh farm and the "
                         "one-chip farm it is compared with")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, requests and kernel inputs")
    args = ap.parse_args()

    from repro.launch.cache import enable_compile_cache
    print(f"[cache] {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    info = device_phase(args.chips)
    if args.chips == 4:
        mesh_phase(**FARM)
    else:
        kernel_phase(args.seed)
        serving_phase(args.seed)
        farm_phase(**FARM)
    print(f"[done] {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
