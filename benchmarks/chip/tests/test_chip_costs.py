"""The benchmark's parameter, operation and byte arithmetic."""

import functools

import jax
import pytest

from conftest import bench, tiny_model
from harness import common, costs

PUBLISHED = {"qwen2-0.5b": 494_032_768, "qwen2-vl-2b": 1_543_714_304}


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_param_count_matches_published_total(name):
    cfg = common.config_file(bench(), name)
    assert costs.param_count(cfg) == PUBLISHED[name]


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_param_count_matches_the_program_and_the_reference(name):
    cfg = tiny_model(name)
    adapter = common.load_module("adapters", cfg["family"])
    ref = common.load_module("references", cfg["family"])
    model = adapter.program_model(cfg)
    program = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    ours = jax.eval_shape(functools.partial(ref.init_weights, cfg),
                          jax.random.PRNGKey(0))
    assert model.param_count(program) == costs.param_count(cfg)
    assert sum(x.size for x in jax.tree.leaves(ours)) == costs.param_count(
        cfg)
    # the benchmark's weights fill the program's tree leaf for leaf
    mapped = adapter.program_params(ours)
    assert jax.tree.structure(mapped) == jax.tree.structure(program)
    for a, b in zip(jax.tree.leaves(mapped), jax.tree.leaves(program)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_token_flops_and_decode_cost():
    cfg = common.config_file(bench(), "qwen2-0.5b")
    d = costs.dims(cfg)
    mm = costs.matmul_params(cfg)
    assert mm == costs.param_count(cfg) - d["L"] * (2 * d["D"] + d["H"] * d[
        "hd"] + 2 * d["K"] * d["hd"]) - d["D"]
    assert costs.token_flops(cfg, 0) == 2 * mm
    assert costs.token_flops(cfg, 10) - costs.token_flops(cfg, 0) == \
        4 * d["L"] * d["H"] * d["hd"] * 10
    assert costs.token_flops(cfg, 0, logits=False) == \
        2 * (mm - d["V"] * d["D"])
    kv = costs.kv_bytes_per_position(cfg)
    assert kv == 2 * 24 * 2 * 64 * 2
    flops, nbytes = costs.decode_step_cost(cfg, [100, 200])
    assert flops == costs.token_flops(cfg, 100) + costs.token_flops(cfg, 200)
    assert nbytes == 2 * mm + 300 * kv
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = costs.least_time(flops, nbytes, peak)
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)
    t, bound = costs.least_time(1e15, 1.0, peak)
    assert bound == "compute" and t == pytest.approx(1e15 / 197e12)


def test_peaks_are_keyed_by_device_kind():
    assert common.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert common.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(common.BenchError):
        common.peaks("TPU v9 imaginary")
