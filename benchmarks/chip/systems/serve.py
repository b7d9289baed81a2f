"""Serving cells: the program's ``ServeEngine`` over ``LocalDecodeBackend``,
driven open-loop by the cell's traffic mix.

Set-up makes the weights on the device from the seed, warms every program
the window runs (each slot's reset, the prefill chunk, the decode step) and
brings the engine to the mix's starting state.  The window submits each
request when it is due and steps the engine; every time is taken on the host
clock around ``step()``, which returns only once its tokens are on the host.
After the window a sample of finished requests, drawn from the seed and
holding the longest, is compared with the plain float32 reference.
"""

from __future__ import annotations

import bisect
import functools
import gc
import json
import time

import numpy as np

from harness import common, tracing, traffic


class CountingBackend:
    """The engine's backend, passed through, counting the work of each call
    on the host: valid cache positions per slot, prompt tokens prefilled,
    and for each decode call its end time and the context lengths of its
    active rows.  Used in traced runs only."""

    def __init__(self, inner, clock):
        self.inner, self.clock = inner, clock
        self.n_slots, self.max_len = inner.n_slots, inner.max_len
        self.prefill_chunk = inner.prefill_chunk
        self.ctx = np.zeros(inner.n_slots, np.int64)
        self.decodes = []       # (end time, context lengths of active rows)
        self.prefills = []      # (end time, context before, prompt tokens)

    def reset(self, slot):
        self.inner.reset(slot)
        self.ctx[slot] = 0

    def prefill(self, slot, toks, act):
        self.inner.prefill(slot, toks, act)
        n = int(np.sum(act))
        self.prefills.append((self.clock(), int(self.ctx[slot]), n))
        self.ctx[slot] += n

    def decode(self, last, adv):
        out = self.inner.decode(last, adv)
        adv = np.asarray(adv, bool)
        self.ctx[adv] += 1
        self.decodes.append((self.clock(), self.ctx[adv].copy()))
        return out

    def close(self):
        self.inner.close()


def run(r) -> None:
    import jax
    from repro.core.trace import TraceRecorder
    from repro.serve import LocalDecodeBackend, Request, ServeEngine

    cfg, mix = r.cfg, r.mix
    traffic.check_fits(mix)
    ref = common.load_module("references", cfg["family"])
    adapter = common.load_module("adapters", cfg["family"])
    model = adapter.program_model(cfg)
    marks = [("start", r.clock.since_start())]
    w = jax.block_until_ready(jax.jit(functools.partial(
        ref.init_weights, cfg))(common.jax_key(r.seed, 1)))
    r.weights = w
    marks.append(("weights", r.clock.since_start()))
    backend = LocalDecodeBackend(
        model, adapter.program_params(w), n_slots=int(mix["n_slots"]),
        max_len=int(mix["max_len"]), prefill_chunk=int(mix["prefill_chunk"]))
    if r.trace:
        backend = CountingBackend(backend, time.perf_counter)
    rec = TraceRecorder(clock=time.perf_counter, enabled=r.trace,
                        capacity=1 << 22)
    eng = ServeEngine(backend, time_fn=time.perf_counter, recorder=rec)
    if r.fault is not None:
        r.fault(backend.inner if r.trace else backend)

    # warm-up: every slot admitted once (each compiles its reset), one
    # prompt of a prefill chunk (the others have no context to prefill),
    # two decode steps
    pc = int(mix["prefill_chunk"])
    for s in range(eng.n_slots):
        prompt = (1,) * (pc + 1 if s == 0 else 1)
        eng.submit(Request(rid=-(s + 1), prompt=prompt, max_new=2))
    eng.run_until_drained()
    marks.append(("warm-up", r.clock.since_start()))

    items = traffic.schedule(mix, r.seed, r.seconds, int(cfg["vocab_size"]))
    req = {it.rid: it for it in items}

    def submit(it):
        eng.submit(Request(rid=it.rid, prompt=tuple(it.prompt.tolist()),
                           max_new=it.max_new))

    fly = [it for it in items if it.group == "in_flight"]
    for it in fly:                      # slots full and staggered
        submit(it)
    if fly:
        eng.step()
    marks.append(("in flight", r.clock.since_start()))
    queue = [it for it in items if it.group != "in_flight"]
    T = float(r.seconds)
    w0 = time.perf_counter() + float(mix.get("preroll_s", 0.0))
    steps = []                          # (end time, tokens emitted)
    late = []                           # submit time - due time
    nxt = 0

    def pump(until: float, arrivals: bool) -> None:
        nonlocal nxt
        while True:
            now = time.perf_counter()
            while arrivals and nxt < len(queue) and \
                    w0 + queue[nxt].due <= now:
                submit(queue[nxt])
                late.append(now - (w0 + queue[nxt].due))
                nxt += 1
            if now >= until:
                return
            n = eng.step()
            if n:
                steps.append((time.perf_counter(), n))
            elif arrivals and nxt < len(queue):
                time.sleep(max(0.0, min(w0 + queue[nxt].due, until)
                               - time.perf_counter()))
            elif not arrivals:
                return
            else:
                time.sleep(min(1e-3, max(0.0, until - now)))

    queue.sort(key=lambda it: it.due)
    pump(w0, True)                      # pre-roll: part of set-up
    r.setup_s = time.perf_counter() - r.clock.t0
    marks.append(("pre-roll", r.setup_s))
    common.log("[setup] " + ", ".join(
        f"{b[0]} {b[1] - a[1]:.2f} s" for a, b in zip(marks, marks[1:]))
        + f" (process start to the first: {marks[0][1]:.2f} s); "
        + r.compiles.since((0, 0, 0.0)))
    in_window = r.compiles.snapshot()
    w1 = w0 + T
    if r.trace:     # the window's last stretch, so stopping costs no window
        pump(w1 - min(T, float(mix.get("trace_s", 10.0))), True)
        with tracing.capture(r.log_dir) as host_start:
            tw0 = time.perf_counter()
            pump(w1, True)
            tw1 = time.perf_counter()
    else:
        pump(w1, True)
    common.log("[window] " + r.compiles.since(in_window))

    # after the close: requests due in the window are awaited (their
    # latency counts the wait); above the knee only the window is timed
    window = [it for it in items if it.group == "window"]
    deadline = time.perf_counter() + float(mix.get("drain_s", 0.0))
    while time.perf_counter() < deadline and any(
            eng.poll(it.rid) is None for it in window):
        n = eng.step()
        if n:
            steps.append((time.perf_counter(), n))
        else:
            break
    end = time.perf_counter()
    r.memory_peak = common.memory_peak(jax.devices()[:r.chips])

    resp = {rid: eng.poll(rid) for rid in req}
    done = {rid: x for rid, x in resp.items() if x is not None}
    step_t = [t for t, _ in steps]

    # end-to-end readings
    ttft, unanswered = [], 0
    for it in window:
        x = done.get(it.rid)
        if x is None or x.first_token_at is None:
            ttft.append(end - (w0 + it.due))
            unanswered += 1
        else:
            ttft.append(x.first_token_at - (w0 + it.due))
    r.data["ttft_s"] = ttft
    r.data["tokens_in_window"] = sum(n for t, n in steps if w0 < t <= w1)
    r.data["window_s"] = T
    r.data["finished"] = len(done)
    r.data["finished_in_window"] = sum(
        1 for x in done.values() if w0 <= x.finished_at <= w1)
    r.data["generator_late_max_s"] = max(late) if late else 0.0
    gaps = []
    for x in done.values():
        if x.first_token_at is None or x.first_token_at < w0:
            continue
        i = bisect.bisect_left(step_t, x.first_token_at)
        ts = step_t[i:i + len(x.tokens)]
        gaps += [b - a for a, b in zip(ts, ts[1:])]
    r.data["itl_s"] = gaps
    wrong = [rid for rid, x in done.items() if len(x.tokens) !=
             req[rid].max_new]
    if mix.get("drain_s", 0):
        # below the knee every request due in the window is awaited
        r.attempted = len(window)
        r.failed = unanswered + sum(1 for it in window if it.rid in wrong)
    else:
        # above it the queue grows: the answers judged are those finished
        r.attempted = len(done)
        r.failed = len(wrong)
    common.log(f"[serve] window {T}s: {len(steps)} steps, "
               f"{r.data['tokens_in_window']} tokens in the window, "
               f"{len(done)} requests finished, {nxt} submitted, "
               f"generator late by up to "
               f"{r.data['generator_late_max_s'] * 1e3:.1f} ms")
    if ttft:
        common.log("[serve] ttft_ms " + json.dumps(
            sorted(round(1e3 * t, 1) for t in ttft)))

    # per-layer readings (traced runs)
    if r.trace:
        events = rec.events()
        r.data["spans"] = [(e.name, e.ts, e.ts + e.dur) for e in events
                           if e.kind == "span"]
        admit = {e.args["rid"]: e.ts for e in events if e.name == "admit"}
        r.data["queue_wait_s"] = [admit[it.rid] - (w0 + it.due)
                                  for it in window if it.rid in admit]
        r.data["occupancy"] = [e.args["active"] / eng.n_slots
                               for e in events if e.name == "decode_chunk"
                               and w0 <= e.ts < w1]
        r.data["decodes"] = backend.decodes
        r.data["prefills"] = backend.prefills
        r.data["window"] = (w0, w1)
        r.data["trace_window"] = (tw0, tw1)
        r.trace_data = tracing.load(r.log_dir, host_start)
        r.devices_used = [0]

    # the check: free the program's state, then the reference
    sample = _sample(done, req, r.seed, int(mix.get("sample", 8)))
    served = [(req[rid].prompt, np.asarray(done[rid].tokens, np.int32))
              for rid in sample]
    # the backend's jitted steps close over it: a cycle, which only the
    # collector frees, and the reference needs the memory it holds
    del eng, backend
    gc.collect()
    r.check_served = served
    _check(r, ref, w, served)


def _sample(done: dict, req: dict, seed: int, k: int) -> list:
    """``k`` finished requests drawn from the seed, the longest among them."""
    real = sorted(rid for rid in done if rid >= 0 and done[rid].tokens)
    if not real:
        return []
    longest = max(real, key=lambda rid: (len(done[rid].tokens), -rid))
    rest = [rid for rid in real if rid != longest]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x5A4D]))
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False) \
        if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


def gap_readings(ref, cfg, w, served, mode: str = "f32") -> dict:
    """The widest gap by which a served token's reference logit lies below
    the reference's best, over every served token of ``served``.  With
    ``mode`` other than f32, the tokens are those that the reference computed
    in that precision puts first, at the same positions (the control)."""
    worst, n, differ = 0.0, 0, 0
    for prompt, toks in served:
        seq = np.concatenate([np.asarray(prompt, np.int32), toks])
        inp, tgt = seq[:-1], seq[1:]
        lo = len(prompt) - 1          # position that predicts toks[0]
        if mode != "f32":
            _, _, am = ref.logit_stats(cfg, w, inp, tgt, mode)
            tgt = np.concatenate([tgt[:lo], am[lo:]])
        mx, at, am = ref.logit_stats(cfg, w, inp, tgt, "f32")
        g = mx[lo:] - at[lo:]
        worst = max(worst, float(g.max()))
        n += len(g)
        differ += int(np.sum(am[lo:] != tgt[lo:]))
    return {"max_logit_gap": worst, "tokens": n, "not_reference_top": differ}


def _check(r, ref, w, served) -> None:
    if not served:
        r.checks.append(("finished_requests_sampled", 0, ">=1"))
        r.correct = False
        return
    g = gap_readings(ref, r.cfg, w, served)
    limit = r.limits["max_logit_gap"]["limit"]
    r.checks.append(("max_logit_gap", g["max_logit_gap"], limit))
    r.correct = g["max_logit_gap"] <= limit
    common.log(f"[check] {len(served)} requests, {g['tokens']} served "
               f"tokens, {g['not_reference_top']} not the reference's top "
               f"token, widest logit gap {g['max_logit_gap']!r}")
