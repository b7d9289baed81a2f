"""The Nemotron-H training cell's harness at test size: a whole run of
``systems/train.py`` through the program's training loop with its control,
the cut's parameter count, the cost arithmetic and the new reader."""

import functools
import types

import jax
import pytest

from conftest import CPU
from harness import common, nemotron_h_costs

CELL = "nemotron-3-nano-30b-a3b.train8k"
CONFIG = "nemotron-3-nano-30b-a3b"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the cell's limits are set at its full widths; at 64 wide, bfloat16
# rounds the loss some ten times coarser, and the Mamba layers' dt_bias
# gradient (a sum over every position that mostly cancels) reads 0.14 to
# 0.37 on sound seeds, against 1.5 to 2.7 for the fp8 reference (seeds
# 2**31 + 7, 11, 13, 17, on the CPU)
LIMITS = {"nonfinite_losses": {"limit": 0}, "loss_rel_err": {"limit": 5e-3},
          "grad_rel_l2_max": {"limit": 0.6},
          "update_rel_l2_max": {"limit": 0.01}}


def tiny_cfg() -> dict:
    """The cell's configuration at widths a CPU test holds: the same seven
    layers, 4 of 16 experts held."""
    cfg = dict(common.config_file(common.benchmark(), CONFIG))
    cfg.update(hidden_size=64, mamba_num_heads=8, mamba_head_dim=8,
               n_groups=2, ssm_state_size=8, chunk_size=16,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               moe_intermediate_size=32,
               moe_shared_expert_intermediate_size=64, n_routed_experts=4,
               n_routed_experts_published=16, vocab_size=256)
    cfg["train"] = dict(cfg["train"], attn_chunk=16, loss_chunk=32)
    return cfg


def test_train_run_at_test_size():
    """A run of the cell reads correct, with its four checks, and the
    control (the reference in fp8, the step in bf16) reads far worse."""
    import run
    b = common.benchmark()
    mix = dict(common.traffic_file("train8k"), batch=2, seq_len=64,
               trace_s=1.0)
    r = run.new_run(b, common.cell(b, CELL), seed=2 ** 31 + 7, seconds=1.5,
                    trace=True, cfg=tiny_cfg(), mix=mix, limits=LIMITS)
    out = run.execute(r, dict(CPU))
    assert out["correct"] is True, out["checks"]
    assert set(out["checks"]) == set(LIMITS)
    steps = r.data["traced_steps"]
    assert steps and all(len(h) == 3 and len(h[0]) == 4
                         for _, _, h in steps)
    # the CPU has no device trace and no peak: those readers read nothing
    assert "held_expert_imbalance.train" in out["metrics"]
    assert "mfu.nemotron_h" not in out["metrics"]
    control = common.load_module("systems", "train").control_readings(r)
    for name in ("grad_rel_l2_max", "update_rel_l2_max"):
        assert control[name] > 3 * out["checks"][name]["value"]
        assert control[name] > LIMITS[name]["limit"]


@pytest.mark.parametrize("plant, caught", [
    ("skip_update", "update_rel_l2_max"),
    ("half_batch", "grad_rel_l2_max")])
def test_planted_fault_in_the_timed_step_is_not_correct(plant, caught):
    """A step that drops its update, or trains on half its batch, comes
    out not correct."""
    import run
    import train_readings
    b = common.benchmark()
    mix = dict(common.traffic_file("train8k"), batch=2, seq_len=64,
               trace_s=1.0)
    with train_readings.planted(plant):
        r = run.new_run(b, common.cell(b, CELL), seed=2 ** 31 + 9,
                        seconds=0.5, trace=False, cfg=tiny_cfg(), mix=mix,
                        limits=LIMITS)
        out = run.execute(r, dict(CPU))
    assert out["correct"] is False
    c = out["checks"][caught]
    assert c["value"] > c["limit"], out["checks"]


def test_param_count_matches_the_cut_the_program_and_the_reference():
    cfg = common.config_file(common.benchmark(), CONFIG)
    assert nemotron_h_costs.param_count(cfg) == 528_093_120
    assert nemotron_h_costs.mamba_params(cfg) == 38_744_896
    assert nemotron_h_costs.expert_params(cfg) == 100_125_440
    assert nemotron_h_costs.attention_params(cfg) == 23_399_040
    small = tiny_cfg()
    ref = common.load_module("references", "nemotron_h")
    adapter = common.load_module("adapters", "nemotron_h")
    model = adapter.program_model(small)
    program = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    ours = jax.eval_shape(functools.partial(ref.init_weights, small),
                          jax.random.PRNGKey(0))
    assert model.param_count(program) == nemotron_h_costs.param_count(small)
    assert sum(x.size for x in jax.tree.leaves(ours)) == \
        nemotron_h_costs.param_count(small)
    mapped = jax.eval_shape(lambda w: adapter.program_params(w, small), ours)
    assert jax.tree.structure(mapped) == jax.tree.structure(program)
    assert jax.tree.map(lambda a: a.shape, mapped) == \
        jax.tree.map(lambda a: a.shape, program)


def test_step_flops_at_the_cell():
    """588.7 MFLOP a token forward at 8192 with the expected 0.375 held
    pairs a token an expert layer (6 of 128 experts chosen, 8 held); the
    step is three times the forward, 28.94 TFLOP; Mamba mixers 41 % of it,
    the expert layers 25 %, attention 19 %, the head 15 %."""
    cfg = common.config_file(common.benchmark(), CONFIG)
    c = nemotron_h_costs
    B, S = 2, 8192
    pairs = B * S * 6 * 8 // 128 * 3
    step = c.step_flops(cfg, B, S, pairs)
    assert step / (3 * B * S) == pytest.approx(588.7e6, rel=1e-4)
    assert step == pytest.approx(28.94e12, rel=1e-3)
    d = c.dims(cfg)
    D = d["D"]
    fwd = step / 3
    mamba = 3 * B * S * (2 * D * (d["di"] + d["conv"] + d["H"])
                         + 2 * d["di"] * D + c.ssd_flops(cfg))
    head = B * S * 2 * D * d["V"]
    attn = B * (S * 2 * (D * (4096 + 2 * 256) + 4096 * D)
                + c.attention_flops(cfg, S))
    assert round(100 * mamba / fwd) == 41
    assert round(100 * (fwd - mamba - head - attn) / fwd) == 25
    assert round(100 * attn / fwd) == 19
    assert round(100 * head / fwd) == 15


def test_mfu_reader():
    cfg = common.config_file(common.benchmark(), CONFIG)
    held = [[10, 20, 30, 40]] * 3
    steps = [(0.0, 0.2, held), (0.21, 0.4, held)]
    r = types.SimpleNamespace(cfg=cfg, trace_data=None, peak=PEAK,
                              data={"traced_steps": steps,
                                    "batch_shape": (2, 8192)})
    read = common.load_module("metrics", "mfu.nemotron_h").read
    flops = 2 * nemotron_h_costs.step_flops(cfg, 2, 8192, 300)
    assert read(r) == pytest.approx(100 * flops / (0.4 * 197e12))
    r.data["traced_steps"] = [(0.0, 0.2, None)]
    assert read(r) is None
    r.peak = None
    assert read(r) is None
