"""Training cells: the program's training loop
(``repro.train.train_loop.train``), closed loop, one step after another.

Set-up makes the weights on the device from the seed
(``references/<family>.py`` ``init_weights``), puts them in the program's
layout (``adapters/<family>.py``) and runs the warm-up steps, the first of
which compiles the step.  The window is the steps after them, until one ends
``--seconds`` after the window's start; each step waits for its loss on the
host (``train`` does).  Every step's batch is ``batch`` sequences of
``seq_len`` ids drawn uniformly over the vocabulary on the device, from the
seed and the step, with next-token labels.

The check: every step's loss is finite; then the timed step program
itself runs once more, from the parameters as they stand after the window,
with the optimizer's moments at zero and its count where the warmup's
learning rate peaks (so the update stands clear of float32's rounding of the
weights), on a fresh seeded batch at the timed shape.  What it produced is
held against the plain reference (:func:`readings`): its loss and its
gradient (the first moment it wrote, over ``1 - b1``) against the float32
reference's loss and clipped gradients; its change of the parameters against
the reference's AdamW step taken from that same gradient.  Every number is
worked out on the device: float64 arithmetic over the parameters on the
host took minutes.
"""

from __future__ import annotations

import contextlib
import functools
import gc
import math
import time

import numpy as np

from harness import common, tracing

WARM = 2                  # warm-up steps: the first compiles the step


class UniformIds:
    """The program's ``TokenSource`` contract: ``create(step)`` gives
    ``{"tokens", "labels"}`` (B, S) int32, uniform ids over ``vocab`` drawn
    on the device from ``key`` and the step."""

    def __init__(self, batch: int, seq: int, vocab: int, key):
        import jax
        import jax.numpy as jnp

        def draw(k, step):
            ids = jax.random.randint(jax.random.fold_in(k, step),
                                     (batch, seq + 1), 0, vocab, jnp.int32)
            return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}

        self.key, self._draw = key, jax.jit(draw)

    def create(self, step: int) -> dict:
        return self._draw(self.key, step)


def optimizer(cfg: dict):
    from repro.train import AdamW
    from repro.train.optimizer import linear_warmup
    t = cfg["train"]
    return AdamW(lr=linear_warmup(float(t["learning_rate"]),
                                  int(t["warmup_steps"])),
                 b1=float(t["adam_b1"]), b2=float(t["adam_b2"]),
                 eps=float(t["adam_eps"]),
                 weight_decay=float(t["weight_decay"]),
                 clip_norm=float(t["clip_norm"]))


def run(r) -> None:
    import jax
    from repro.core.trace import TraceRecorder
    from repro.train import train

    cfg, mix = r.cfg, r.mix
    adapter = common.load_module("adapters", cfg["family"])
    model = adapter.program_model(cfg)
    ref = common.load_module("references", cfg["family"])
    marks = [("start", r.clock.since_start())]
    w = jax.block_until_ready(jax.jit(functools.partial(
        ref.init_weights, cfg))(common.jax_key(r.seed, 1)))
    params = jax.block_until_ready(adapter.program_params(w, cfg))
    del w
    marks.append(("weights", r.clock.since_start()))
    B, S, V = int(mix["batch"]), int(mix["seq_len"]), int(cfg["vocab_size"])
    source = UniformIds(B, S, V, common.jax_key(r.seed, 2))
    rec = TraceRecorder(clock=time.perf_counter, enabled=r.trace,
                        capacity=1 << 20)
    T = float(r.seconds)
    trace_s = min(T, float(mix.get("trace_s", 8.0)))
    st = {"w0": None, "done": [], "losses": [], "tw0": None, "tw1": None,
          "host_start": None, "snap": None}
    profile = contextlib.ExitStack()

    def on_step(i, params, opt_state, metrics) -> bool:
        now = time.perf_counter()
        st["losses"].append(float(metrics["loss"]))
        if i < WARM - 1:
            return False
        if i == WARM - 1:          # set-up ends: the window starts
            r.setup_s = now - r.clock.t0
            marks.append(("warm-up", r.setup_s))
            common.log("[setup] " + ", ".join(
                f"{b[0]} {b[1] - a[1]:.2f} s"
                for a, b in zip(marks, marks[1:]))
                + f" (process start to the first: {marks[0][1]:.2f} s); "
                + r.compiles.since((0, 0, 0.0)))
            st["snap"] = r.compiles.snapshot()
            st["w0"] = now
            if r.trace:
                st["host_start"] = profile.enter_context(
                    tracing.capture(r.log_dir))
                st["tw0"] = time.perf_counter()
            return False
        st["done"].append(now)
        if st["tw0"] is not None and st["tw1"] is None \
                and now >= st["tw0"] + trace_s:
            st["tw1"] = time.perf_counter()
            profile.close()
        return now >= st["w0"] + T

    opt = optimizer(cfg)
    res = train(model, source, steps=1 << 30, opt=opt, params=params,
                log_every=1 << 30, on_step=on_step, recorder=rec)
    del params
    if st["tw0"] is not None and st["tw1"] is None:
        st["tw1"] = time.perf_counter()
        profile.close()
    common.log("[window] " + r.compiles.since(st["snap"]))
    w0, done = st["w0"], st["done"]
    r.data["batches"] = len(done)
    r.data["window_s"] = done[-1] - w0
    r.data["batch_shape"] = (B, S)
    r.memory_peak = common.memory_peak(jax.devices()[:r.chips])
    stats = jax.devices()[0].memory_stats() or {}
    common.log(f"[train] {len(done)} steps in {r.data['window_s']:.3f} s "
               f"after {WARM} warm-up; loss {st['losses'][0]:.4f} -> "
               f"{st['losses'][-1]:.4f}; peak memory {r.memory_peak} bytes "
               f"in use, {stats.get('peak_bytes_reserved', 0)} reserved for "
               f"programs, of {stats.get('bytes_limit', 0)}")
    if r.trace:
        events = rec.events()
        tw0, tw1 = st["tw0"], st["tw1"]
        r.data["spans"] = [(e.name, e.ts, e.ts + e.dur) for e in events
                           if e.kind == "span"]
        held = {e.args["step"]: e.args["value"] for e in events
                if e.name == "held_expert_tokens"}
        # (start, end, held_expert_tokens) of the steps inside the stretch
        r.data["traced_steps"] = [
            (e.ts, e.ts + e.dur, held.get(e.args["step"])) for e in events
            if e.name == "train_step" and tw0 <= e.ts
            and e.ts + e.dur <= tw1]
        r.data["trace_window"] = (tw0, tw1)
        r.trace_data = tracing.load(r.log_dir, st["host_start"])
        r.devices_used = [0]
    bad = sum(1 for x in st["losses"] if not math.isfinite(x))
    r.attempted = len(st["losses"])
    r.failed = bad
    r.checks.append(("nonfinite_losses", bad,
                     r.limits["nonfinite_losses"]["limit"]))
    _check(r, ref, adapter, res, opt, B, S, V)


def _check(r, ref, adapter, res, opt, B, S, V) -> None:
    """The timed step run once more, and what it produced against the
    reference (module docstring).  Everything stays on the device but one
    copy of the parameters, which the step donates."""
    import jax
    import jax.numpy as jnp
    cfg, t = r.cfg, r.cfg["train"]
    count = int(t["warmup_steps"])
    step_fn, params = res["step_fn"], res["params"]
    res.clear()                 # the optimizer's state
    gc.collect()
    snap = r.compiles.snapshot()
    before = jax.device_get(params)
    state = dict(opt.init(params), step=jnp.asarray(count, jnp.int32))
    batch = UniformIds(B, S, V, common.jax_key(r.seed, 3)).create(0)
    params, state, metrics = step_fn(params, state, batch)
    loss, gnorm = float(metrics["loss"]), float(metrics["grad_norm"])
    one_b1 = np.float32(1 - float(t["adam_b1"]))
    grads = jax.jit(lambda m: jax.tree.map(lambda x: x / one_b1, m))(
        state["m"])
    del state, metrics
    p0 = jax.device_put(before)
    del before
    update = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(
        params, p0)
    del params
    upd, upd_at = _worst(update, ref.adamw_update(cfg, p0, grads, count))
    del update
    w = adapter.reference_weights(p0, cfg)
    tokens, labels = batch["tokens"], batch["labels"]
    t0 = time.perf_counter()
    loss_r, grads_r = ref.loss_and_grads(cfg, w, tokens, labels, "f32")
    reference = {"loss": float(loss_r), "grads": ref.clip_grads(
        cfg, adapter.program_params(grads_r, cfg))}
    del grads_r
    grad, grad_at = _worst(grads, reference["grads"])
    lr = reference["loss"]
    got = {"loss_rel_err": abs(loss - lr) / abs(lr) if math.isfinite(loss)
           else math.inf, "grad_rel_l2_max": grad,
           "update_rel_l2_max": upd}
    common.log(f"[check] reference {time.perf_counter() - t0:.1f} s; loss "
               f"{loss!r} against {lr!r}; gradient norm {gnorm!r} before "
               f"clipping; worst gradient {grad_at}, worst update {upd_at}; "
               f"{r.compiles.since(snap)}")
    for name in ("loss_rel_err", "grad_rel_l2_max", "update_rel_l2_max"):
        r.checks.append((name, got[name], r.limits[name]["limit"]))
    r.correct = all(v <= lim for _, v, lim in r.checks)
    r.check_state = {"w": w, "tokens": tokens, "labels": labels,
                     "p0": p0, "grads": grads, "count": count,
                     "reference": reference}


def _rel_l2(a, b):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(lambda x, y: jnp.sqrt(
        jnp.sum(jnp.square(x - y)) / jnp.maximum(jnp.sum(jnp.square(y)),
                                                 1e-30)), a, b)


def _worst(a, b) -> tuple:
    """The largest relative L2 error of tree ``a`` against ``b`` over their
    tensors (a stacked tensor of several layers counts as one), float32 on
    the device, and the tensor's path."""
    import jax
    errs = jax.device_get(jax.jit(_rel_l2)(a, b))
    worst, name = 0.0, None
    for path, e in jax.tree_util.tree_leaves_with_path(errs):
        e = float(e)
        if not math.isfinite(e) or e > worst:
            worst, name = (e if math.isfinite(e) else math.inf), \
                jax.tree_util.keystr(path)
    return worst, name


def control_readings(r) -> dict:
    """The check with the reference in the precision below the
    configuration's put in the program's place, on the same parameters and
    batch: the loss and gradients in ``fp8`` against the float32
    reference's, the AdamW step in ``bf16`` against the float32 one, from
    the program's gradient.  Needs a run whose check ran."""
    s = r.check_state
    cfg = r.cfg
    ref = common.load_module("references", cfg["family"])
    adapter = common.load_module("adapters", cfg["family"])
    loss, grads = ref.loss_and_grads(cfg, s["w"], s["tokens"], s["labels"],
                                     "fp8")
    grad, grad_at = _worst(ref.clip_grads(cfg, adapter.program_params(
        grads, cfg)), s["reference"]["grads"])
    del grads
    upd, upd_at = _worst(
        ref.adamw_update(cfg, s["p0"], s["grads"], s["count"], "bf16"),
        ref.adamw_update(cfg, s["p0"], s["grads"], s["count"]))
    lr = s["reference"]["loss"]
    return {"loss_rel_err": abs(float(loss) - lr) / abs(lr),
            "grad_rel_l2_max": grad, "grad_rel_l2_worst": grad_at,
            "update_rel_l2_max": upd, "update_rel_l2_worst": upd_at}
