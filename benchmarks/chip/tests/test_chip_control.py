"""The controls: the reference computed in the precision below the one the
configuration states, put in the program's place, reads worse than the
program does.  On the chip the control is read at each cell's own size
(``calibrate.py readings``); here at test size."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from conftest import tiny_farm, tiny_model
from harness import common


def test_serving_control_reads_far_above_the_program(small_run):
    r, out = small_run("qwen2-0.5b.chat", seconds=2.0)
    assert out["correct"] is True
    cfg = r.cfg
    ref = common.load_module("references", cfg["family"])
    serve = common.load_module("systems", "serve")
    w = jax.jit(functools.partial(ref.init_weights, cfg))(
        common.jax_key(r.seed, 1))
    program = serve.gap_readings(ref, cfg, w, r.check_served)
    control = serve.gap_readings(ref, cfg, w, r.check_served, mode="fp8")
    assert program["max_logit_gap"] == out["checks"]["max_logit_gap"][
        "value"]
    assert control["max_logit_gap"] > 3 * program["max_logit_gap"]
    assert control["not_reference_top"] > program["not_reference_top"]


def test_reference_matches_the_program_in_float32():
    """At float32 compute the program's full forward and the plain
    reference agree to rounding: the two follow one description."""
    cfg = dict(tiny_model("qwen2-vl-2b"),
               served={"param_dtype": "float32", "compute_dtype": "float32"})
    ref = common.load_module("references", cfg["family"])
    adapter = common.load_module("adapters", cfg["family"])
    w = jax.jit(functools.partial(ref.init_weights, cfg))(
        jax.random.PRNGKey(3))
    toks = np.random.default_rng(0).integers(0, 4096, 40).astype(np.int32)
    model = adapter.program_model(cfg)
    with jax.default_matmul_precision("highest"):
        logits, _ = model.forward(adapter.program_params(w),
                                  jnp.asarray(toks)[None])
    logits = np.asarray(logits[0])
    mx, at, am = ref.logit_stats(cfg, w, toks, toks)
    np.testing.assert_allclose(mx, logits.max(-1), atol=2e-5)
    np.testing.assert_allclose(at, logits[np.arange(40), toks], atol=2e-5)
    np.testing.assert_array_equal(am, logits.argmax(-1))


def test_farm_control_differs_from_the_reference():
    cfg = tiny_farm()
    ref = common.load_module("references", cfg["family"])
    W, I = int(cfg["width"]), int(cfg["iters"])
    rows = jnp.arange(-16, 112, dtype=jnp.int32)
    args = (W, float(cfg["x0"]), float(cfg["y0"]), float(cfg["span"]) / W, I)
    exact = np.asarray(ref.counts(rows, *args))
    control = np.asarray(ref.counts(rows, *args, "bfloat16"))
    assert int(np.sum(exact != control)) > 0
