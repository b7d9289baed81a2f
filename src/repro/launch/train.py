"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs the training loop — which is the GPP network
``Emit(data) → OneFanAny(batch axes) → Worker(train_step) → AnyFanOne →
Collect(metrics)`` — with checkpointing and fault-tolerant restart.

On this CPU container use ``--reduced`` (the smoke-scale config); on a real
fleet the same entry point runs the full config against the production mesh
(``--mesh single|multi``).
"""

from __future__ import annotations

import argparse
import json
import os

from .cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU-runnable)")
    ap.add_argument("--mesh", default="none",
                    choices=("none", "single", "multi"),
                    help="production mesh (needs real devices or dry-run "
                         "host-device override)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seq-shard", action="store_true",
                    help="sequence-parallel activations (perf lever)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.mesh == "multi":
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=512")

    import jax

    from repro.configs import get_config
    from repro.data import SyntheticLM
    from repro.models import Model
    from repro.parallel.axes import shard_ctx
    from repro.train import AdamW, Checkpointer, cosine_warmup, train
    from repro.train.train_loop import as_network
    from repro.core import verify
    from .mesh import make_production_mesh, train_rules

    import dataclasses
    cfg = get_config(args.arch, reduced=args.reduced)
    cfg = dataclasses.replace(cfg, seq_shard=args.seq_shard)
    model = Model(cfg)
    opt = AdamW(lr=cosine_warmup(args.lr, warmup=max(args.steps // 20, 1),
                                 total=args.steps))
    # the network formulation is verified before anything runs (gppBuilder)
    net = as_network(model, opt, grad_accum=args.grad_accum)
    report = verify(net)
    print(f"[train] network {net.name} verified: {report.checks}")

    mesh = None
    if args.mesh != "none":
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")
    source = SyntheticLM(batch=args.batch, seq=args.seq, vocab=cfg.vocab)
    ckpt = Checkpointer(args.ckpt_dir, async_save=True) \
        if args.ckpt_dir else None

    rules = train_rules(cfg.seq_shard)
    ctx = shard_ctx(mesh, rules) if mesh is not None else None
    if ctx:
        ctx.__enter__()
    try:
        res = train(model, source, steps=args.steps, opt=opt, mesh=mesh,
                    grad_accum=args.grad_accum, checkpointer=ckpt,
                    ckpt_every=args.ckpt_every if ckpt else 0)
    finally:
        if ctx:
            ctx.__exit__(None, None, None)
    if ckpt:
        ckpt.wait()
    print(json.dumps(res["history"], indent=1))
    print(f"[train] {args.arch}: loss "
          f"{res['history'][0]['loss']:.4f} -> {res['history'][-1]['loss']:.4f} "
          f"in {res['step']} steps")


if __name__ == "__main__":
    main()
