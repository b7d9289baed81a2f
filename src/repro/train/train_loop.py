"""Training step + loop.

``make_train_step`` builds the pure step function (grad-accumulation via an
inner scan, mixed precision per config).  ``as_network`` exposes the same
step as a GPP network — the paper's fundamental pattern with training stages
as processes: Emit(data) → OneFanAny(batch axes) → Worker(fwd/bwd+update) →
AnyFanOne → Collect(metrics) — which is what launch/train.py actually runs:
the framework's training loop *is* a built pattern, not merely analogous to
one.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import (AnyFanOne, Collect, Emit, Network, OneFanAny, Worker,
                        build)
from repro.core import trace as _trace
from repro.core.stream import stack_microbatches
from repro.models import Model
from .optimizer import AdamW

__all__ = ["TrainState", "make_train_step", "as_network", "train"]


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: int = 0


def make_train_step(model: Model, opt: AdamW, *,
                    grad_accum: int = 1) -> Callable:
    """Returns step(params, opt_state, batch) -> (params, opt_state, metrics).

    ``grad_accum > 1`` splits the global batch into microbatches along the
    leading axis and accumulates grads in f32 with a lax.scan (memory lever).
    Jitted, the step is the device program ``jit_train_step``.
    """

    def loss(params, batch):
        return model.loss_fn(params, batch)

    def train_step(params, opt_state, batch):
        if grad_accum == 1:
            (l, metrics), grads = jax.value_and_grad(
                loss, has_aux=True)(params, batch)
        else:
            # the streaming runtime's microbatch schedule: grad accumulation
            # is the same splitter, scanned instead of dispatched
            mb = stack_microbatches(batch, grad_accum)

            def body(acc, mbatch):
                (l, m), g = jax.value_and_grad(loss, has_aux=True)(
                    params, mbatch)
                acc_g, acc_l = acc
                acc_g = jax.tree_util.tree_map(
                    lambda a, x: a + x.astype(jnp.float32), acc_g, g)
                return (acc_g, acc_l + l), m

            zero_g = jax.tree_util.tree_map(
                lambda x: jnp.zeros_like(x, jnp.float32), params)
            (g_sum, l_sum), ms = jax.lax.scan(
                body, (zero_g, jnp.asarray(0.0, jnp.float32)), mb)
            grads = jax.tree_util.tree_map(lambda x: x / grad_accum, g_sum)
            l = l_sum / grad_accum
            metrics = jax.tree_util.tree_map(lambda x: x[-1], ms)
            if "held_expert_tokens" in ms:
                metrics["held_expert_tokens"] = jnp.sum(
                    ms["held_expert_tokens"], axis=0)
        new_params, new_opt, stats = opt.update(grads, opt_state, params)
        metrics = dict(metrics, loss=l, **stats)
        return new_params, new_opt, metrics

    return train_step


def as_network(model: Model, opt: AdamW, *, grad_accum: int = 1,
               batch_axis: Any = ("pod", "data")) -> Network:
    """The training step as a GPP network (declaration mirrors Listing 3).

    The Worker carries (params, opt_state, batch) packed as the item; the
    Collect keeps the latest metrics.  launch/train.py builds this with the
    production mesh so the OneFanAny's axis is the (pod, data) batch axes.
    """
    step = make_train_step(model, opt, grad_accum=grad_accum)

    def worker_fn(item):
        params, opt_state, batch = item
        p2, o2, metrics = step(params, opt_state, batch)
        return (p2, o2, metrics)

    net = Network(f"train[{model.cfg.name}]")
    net.add(
        Emit(lambda i: None, name="emit"),
        OneFanAny(axis=batch_axis, name="spread"),
        Worker(worker_fn, batched=True, name="train_step"),
        AnyFanOne(name="merge"),
        Collect(lambda acc, item: item[2], init=None, jit_combine=False,
                name="collect"),
    )
    return net


def train(model: Model, source, *, steps: int, opt: Optional[AdamW] = None,
          mesh=None, grad_accum: int = 1, key=None,
          checkpointer=None, ckpt_every: int = 0, params=None,
          opt_state: Any = None, start_step: int = 0,
          log_every: int = 10, on_step=None, recorder=None) -> dict:
    """The end-to-end loop used by examples and launch/train.py.

    ``on_step(i, params, opt_state, metrics)`` runs after each step's
    dispatch; a true return ends the loop there.  ``recorder`` (a
    :class:`~repro.core.trace.TraceRecorder`, default the process's) gets,
    when enabled, a ``data`` span around each ``source.create``, a
    ``train_step`` span from the step's dispatch to its loss on the host
    (the one wait the loop adds, and only when recording), and, where the
    expert layers hold a share, a ``held_expert_tokens`` counter: the tokens
    routed to each held expert in the step, a list per expert layer.

    Returns {"params", "opt_state", "history", "step", "step_fn"}: ``step``
    is one past the last step run, ``step_fn`` the jitted step (it donates
    its params and opt_state)."""
    opt = opt or AdamW()
    key = key if key is not None else jax.random.PRNGKey(0)
    if params is None:
        params = model.init(key)
    if opt_state is None:
        opt_state = opt.init(params)
    rec = recorder if recorder is not None else _trace.current()
    step_fn = jax.jit(make_train_step(model, opt, grad_accum=grad_accum),
                      donate_argnums=(0, 1))
    history = []
    t0 = time.monotonic()
    end = start_step + steps
    for i in range(start_step, start_step + steps):
        with rec.span("data", "train", step=i):
            batch = source.create(i)
            if mesh is not None:
                from repro.data.pipeline import shard_batch
                batch = shard_batch(batch, mesh)
        with rec.span("train_step", "train", step=i):
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            if rec.enabled:
                float(metrics["loss"])
        held = metrics.pop("held_expert_tokens", None)
        if held is not None and rec.enabled:
            rec.counter("held_expert_tokens", jax.device_get(held).tolist(),
                        "train", step=i)
        stop = on_step is not None and on_step(i, params, opt_state,
                                               metrics)
        if ckpt_every and checkpointer is not None \
                and (i + 1) % ckpt_every == 0:
            checkpointer.save(i + 1, {"params": params,
                                      "opt_state": opt_state})
        if (i - start_step) % log_every == 0 or i == end - 1 or stop:
            m = {k: float(v) for k, v in metrics.items()}
            m["step"] = i
            m["wall_s"] = time.monotonic() - t0
            history.append(m)
        if stop:
            end = i + 1
            break
    return {"params": params, "opt_state": opt_state, "history": history,
            "step": end, "step_fn": step_fn}
