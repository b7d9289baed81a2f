"""End-to-end LM training driver (deliverable b).

Trains a decoder LM on the synthetic stream with the full substrate engaged:
the GPP-network train step, AdamW + cosine schedule, grad accumulation,
atomic checkpointing with an injected mid-run failure + automatic restart.

Sizes:
  --size tiny   ~4M params, 200 steps  → a couple of minutes on CPU
  --size 100m   ~100M params (d=640, L=12) — the "train a ~100M model"
                 configuration; a few hundred steps are hours on one CPU
                 core, so default steps stay small unless overridden.

    PYTHONPATH=src python examples/train_lm.py --size tiny --steps 200
"""

import argparse
import dataclasses
import tempfile

import jax

from repro.configs.base import ModelConfig
from repro.data import SyntheticLM
from repro.launch.cache import enable_compile_cache
from repro.models import Model
from repro.train import (AdamW, Checkpointer, FaultInjector,
                         FaultTolerantRunner, cosine_warmup, make_train_step)
from repro.train.train_loop import as_network
from repro.core import verify

SIZES = {
    "tiny": dict(n_layers=4, d_model=256, n_heads=4, n_kv_heads=2,
                 d_ff=1024, vocab=2048),
    "100m": dict(n_layers=12, d_model=640, n_heads=10, n_kv_heads=2,
                 d_ff=2560, vocab=32_000),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", choices=SIZES, default="tiny")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--inject-failure", action="store_true", default=True)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = ModelConfig(name=f"lm-{args.size}", family="dense",
                      qkv_bias=False, tied_embeddings=True,
                      param_dtype="float32", compute_dtype="float32",
                      remat="none", **SIZES[args.size])
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_params = model.param_count(params)
    print(f"[train_lm] {cfg.name}: {n_params/1e6:.1f}M params, "
          f"{args.steps} steps of batch {args.batch}×{args.seq}")

    opt = AdamW(lr=cosine_warmup(args.lr, warmup=args.steps // 10,
                                 total=args.steps))
    # the train step as a verified GPP network
    verify(as_network(model, opt, grad_accum=args.grad_accum))

    src = SyntheticLM(batch=args.batch, seq=args.seq, vocab=cfg.vocab)
    step_j = jax.jit(make_train_step(model, opt, grad_accum=args.grad_accum),
                     donate_argnums=(0, 1))
    state = {"params": params, "opt_state": opt.init(params)}
    losses = []

    def step_fn(i, st):
        batch = src.create(i)
        p, o, metrics = step_j(st["params"], st["opt_state"], batch)
        if i % max(args.steps // 10, 1) == 0 or i == args.steps - 1:
            print(f"  step {i:>5}  loss {float(metrics['loss']):.4f}  "
                  f"ppl {float(metrics['perplexity']):.1f}  "
                  f"|g| {float(metrics['grad_norm']):.2f}  "
                  f"lr {float(metrics['lr']):.2e}")
        losses.append(float(metrics["loss"]))
        return {"params": p, "opt_state": o}

    with tempfile.TemporaryDirectory() as ckdir:
        runner = FaultTolerantRunner(Checkpointer(ckdir, async_save=True),
                                     max_restarts=3)
        injector = FaultInjector(
            fail_at=(args.steps // 2,) if args.inject_failure else ())
        state = runner.run(total_steps=args.steps, state=state,
                           step_fn=step_fn,
                           save_every=max(args.steps // 10, 1),
                           injector=injector)
        runner.ckpt.wait()
        print(f"[train_lm] done. restarts survived: {runner.restarts}; "
              f"loss {losses[0]:.4f} → {losses[-1]:.4f}")
        assert losses[-1] < losses[0], "no learning happened"


if __name__ == "__main__":
    main()
