"""The training cell's harness at test size: whole runs of
``systems/train.py`` through the program's training loop, its control, its
cost arithmetic and its readers."""

import functools
import types

import jax
import pytest

from conftest import CPU
from harness import common, train_costs

CELL = "deepseek-v2-lite.train8k"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the cell's limits are set at its full widths; at 64 wide, bfloat16
# rounds the loss some ten times coarser (about 5e-4 of it)
LIMITS = {"nonfinite_losses": {"limit": 0}, "loss_rel_err": {"limit": 5e-3},
          "grad_rel_l2_max": {"limit": 0.3},
          "update_rel_l2_max": {"limit": 0.01}}


def tiny_cfg() -> dict:
    """The cell's configuration at widths a CPU test holds."""
    cfg = dict(common.config_file(common.benchmark(), "deepseek-v2-lite"))
    cfg.update(hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               intermediate_size=128, moe_intermediate_size=32,
               n_routed_experts=4, n_routed_experts_published=16,
               num_hidden_layers=3, vocab_size=256)
    cfg["train"] = dict(cfg["train"], attn_chunk=16, loss_chunk=32)
    return cfg


@pytest.fixture(scope="module")
def train_run():
    """``train_run(trace)``: a run of the cell at test size, with limits
    for that size, and its result line."""
    import run

    def make(trace: bool, seconds: float = 2.0):
        b = common.benchmark()
        mix = dict(common.traffic_file("train8k"), batch=2, seq_len=64,
                   trace_s=1.0)
        r = run.new_run(b, common.cell(b, CELL), seed=2 ** 31 + 5,
                        seconds=seconds, trace=trace, cfg=tiny_cfg(),
                        mix=mix, limits=LIMITS)
        return r, run.execute(r, dict(CPU))

    return make


def test_train_run_at_test_size(train_run):
    r, out = train_run(False)
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"batch_ms", "setup_s"}
    assert r.data["batches"] >= 1 and out["failed"] == 0
    assert out["metrics"]["batch_ms"]["value"] == pytest.approx(
        1e3 * r.data["window_s"] / r.data["batches"])
    assert set(out["checks"]) == {"nonfinite_losses", "loss_rel_err",
                                  "grad_rel_l2_max", "update_rel_l2_max"}
    # the control: the reference in fp8 (the step in bf16) in the
    # program's place reads far worse than the program does
    control = common.load_module("systems", "train").control_readings(r)
    for name in ("grad_rel_l2_max", "update_rel_l2_max"):
        assert control[name] > 3 * out["checks"][name]["value"]
        assert control[name] > LIMITS[name]["limit"]


@pytest.mark.parametrize("plant, caught", [
    ("skip_update", "update_rel_l2_max"),
    ("half_batch", "grad_rel_l2_max")])
def test_planted_fault_in_the_timed_step_is_not_correct(train_run, plant,
                                                        caught):
    """The check reads what the timed step program produced: a step that
    drops its update, or trains on half its batch, comes out not
    correct."""
    import train_readings
    with train_readings.planted(plant):
        _, out = train_run(False, seconds=0.5)
    assert out["correct"] is False
    c = out["checks"][caught]
    assert c["value"] > c["limit"], out["checks"]


def test_traced_train_run_reads_the_counter_and_spans(train_run):
    r, out = train_run(True)
    assert out["correct"] is True
    steps = r.data["traced_steps"]
    assert steps and all(h is not None for _, _, h in steps)
    assert all(len(h) == 2 and len(h[0]) == 4 for _, _, h in steps)
    names = {n for n, _, _ in r.data["spans"]}
    assert {"data", "train_step"} <= names
    # the CPU has no device trace and no peak: those readers read nothing
    assert "held_expert_imbalance.train" in out["metrics"]
    assert "mfu.train" not in out["metrics"]


def test_param_count_matches_the_cut_the_program_and_the_reference():
    cfg = common.config_file(common.benchmark(), "deepseek-v2-lite")
    assert train_costs.param_count(cfg) == 635_466_752
    small = tiny_cfg()
    ref = common.load_module("references", "deepseek_v2")
    adapter = common.load_module("adapters", "deepseek_v2")
    model = adapter.program_model(small)
    program = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    ours = jax.eval_shape(functools.partial(ref.init_weights, small),
                          jax.random.PRNGKey(0))
    assert model.param_count(program) == train_costs.param_count(small)
    assert sum(x.size for x in jax.tree.leaves(ours)) == \
        train_costs.param_count(small)
    mapped = jax.eval_shape(lambda w: adapter.program_params(w, small), ours)
    assert jax.tree.structure(mapped) == jax.tree.structure(program)


def test_step_flops_at_the_cell():
    """843 MFLOP a token forward at 8192 with the expected 0.75 held pairs
    a token an expert layer; the step is three times the forward."""
    cfg = common.config_file(common.benchmark(), "deepseek-v2-lite")
    B, S = 2, 8192
    pairs = B * S * 6 * 8 // 64 * 5
    per_token = train_costs.step_flops(cfg, B, S, pairs) / (3 * B * S)
    assert per_token == pytest.approx(843e6, rel=2e-3)
    assert train_costs.step_flops(cfg, B, S, pairs) == pytest.approx(
        41.4e12, rel=2e-3)


def _r(**data):
    return types.SimpleNamespace(
        cfg=common.config_file(common.benchmark(), "deepseek-v2-lite"),
        data=data, trace_data=None, peak=PEAK, setup_s=1.0)


def _read(name, r):
    return common.load_module("metrics", name).read(r)


def test_train_readers():
    cfg = common.config_file(common.benchmark(), "deepseek-v2-lite")
    held = [[10, 20, 30, 40]] * 2
    steps = [(0.0, 0.2, held), (0.21, 0.4, held)]
    r = _r(traced_steps=steps, batch_shape=(2, 8192))
    flops = 2 * train_costs.step_flops(cfg, 2, 8192, 200)
    assert _read("mfu.train", r) == pytest.approx(
        100 * flops / (0.4 * 197e12))
    assert _read("held_expert_imbalance.train", r) == pytest.approx(1.6)
    r.trace_data = {"offset_s": 0.0, "devices": [{
        "name": "/device:TPU:0", "programs": [
            ["jit_train_step", 0.0, 0.19], ["jit_draw", 0.195, 0.001],
            ["jit_train_step", 0.21, 0.17]]}]}
    r.data["trace_window"] = (0.0, 0.4)
    assert _read("step_device_ms.train", r) == pytest.approx(180.0)
    assert _read("idle_share.train", r) == pytest.approx(
        100 * (1 - 0.361 / 0.4))
    # a step without its counter: nothing to read
    r.data["traced_steps"] = [(0.0, 0.2, None)]
    assert _read("mfu.train", r) is None
    assert _read("held_expert_imbalance.train", r) is None
