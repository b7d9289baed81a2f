"""A run whose timed path is broken underneath comes out not correct.

Each test skips the harness's look for a chip, plants one fault in the
program as the run drives it, and runs the rest of the run at test size:
a token or an answer altered where it is produced, a step that returns its
state unchanged, the exchange between chips left out, and an answer
delivered twice.  The serving cells are the tests' own
(``data/serving.json``).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SERVING = ["qwen2-0.5b.chat", "qwen2-vl-2b.decode"]


def alter_tokens(backend):
    """Every token the decode step produces is replaced by the next id."""
    decode = backend.decode

    def altered(last, adv):
        nxt = decode(last, adv)
        return (np.asarray(nxt) + 1) % backend.model.cfg.vocab

    backend.decode = altered


def state_unchanged(backend):
    """The decode step computes its tokens but hands back the cache it was
    given, so nothing the step wrote is kept."""
    model = backend.model

    def step(params, cache, tokens, advance):
        logits, _ = model.decode_step(params, cache, tokens[:, None],
                                      advance=advance)
        return jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32), cache

    backend._decode = jax.jit(step)


@pytest.mark.parametrize("cell", SERVING)
@pytest.mark.parametrize("fault", [alter_tokens, state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_serving_fault_is_not_correct(small_run, cell, fault):
    r, out = small_run(cell, seconds=2.0, fault=fault)
    assert out["correct"] is False, out["checks"]
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def answer_altered(dep):
    """The worker's counts are off by one on some pixels: its answer is
    wrong where it is produced."""
    proc = dep.net.procs["group"]

    def wrong(row0, fn=proc.fn):
        r0, cnt = fn(row0)
        return r0, cnt + (cnt % 7 == 3).astype(cnt.dtype)

    dep.net.procs["group"] = dataclasses.replace(proc, fn=wrong)


def exchange_left_out(dep):
    """What the worker sends across is never moved: the consumer receives
    zeros in its place."""
    t = dep.transport
    send = t._send_transform

    def dropped(chan, value):
        if chan[0] == "group":
            return jax.tree.map(jnp.zeros_like, value)
        return send(chan, value)

    t._send_transform = dropped


def band_delivered_twice(dep):
    """The first band of every batch reaches the collector twice, as a
    replayed record would; the image collected is the same."""
    proc = dep.net.procs["collect"]

    def twice(acc, item, fold=proc.fn):
        if not acc:
            acc = fold(acc, item)
        return fold(acc, item)

    dep.net.procs["collect"] = dataclasses.replace(proc, fn=twice)


@pytest.mark.parametrize(
    "fault", [answer_altered, exchange_left_out, band_delivered_twice],
    ids=["answer_altered", "exchange_left_out", "band_delivered_twice"])
def test_farm_fault_is_not_correct(small_run, fault):
    r, out = small_run("mandelbrot-farm.jaxmesh4", seconds=1.0, fault=fault)
    assert out["correct"] is False, out["checks"]
    c = out["checks"]
    assert c["mismatched_pixels"]["value"] > 0 or \
        c["misdelivered_bands"]["value"] > 0
    if fault is band_delivered_twice:
        assert c["mismatched_pixels"]["value"] == 0
        assert c["misdelivered_bands"]["value"] == r.data["batches"]
