"""Compile-only guards: the Pallas kernels of the main path, compiled by the
TPU compiler for a described (not attached) v5e chip at real widths.

Interpret mode accepts what Mosaic refuses — blocks that break the (8, 128)
tiling, kernels over the VMEM budget, primitives with no TPU lowering — so
these tests are what catches such a kernel before it reaches a chip.  Nothing
runs; each test only compiles.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.mandelbrot import ops as mb_ops
from repro.kernels.moe_gmm import ops as gmm_ops
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.kernels.stencil import ops as st_ops


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with kernel dispatch steered to
    the compiled path (``jax.default_backend()`` still reports the CPU here)
    and the persistent compile cache off: a compile for a described chip is
    written to it but can never be read back without one."""
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        mp.setattr(jax, "default_backend", lambda: "tpu")
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        jax.clear_caches()  # no interpreted trace of these shapes may hit
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.clear_caches()
            jax.config.update("jax_enable_compilation_cache", cache_on)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn, out_shardings=one_chip).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "kernel not compiled"
    return compiled


@pytest.mark.parametrize("S", [2048, 4096])
def test_flash_attention_qwen2_0p5b_prefill(one_chip, S):
    cfg = get_config("qwen2-0.5b")
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    bf16 = jnp.bfloat16
    _compile(lambda q, k, v: fa_ops.mha(q, k, v, causal=True), one_chip,
             ((1, H, S, hd), bf16), ((1, K, S, hd), bf16),
             ((1, K, S, hd), bf16))


@pytest.mark.parametrize("proj", ["gate_up", "down"])
def test_moe_gmm_deepseek_moe_16b(one_chip, proj):
    moe = get_config("deepseek-moe-16b").moe
    D, F = 2048, moe.d_expert
    if proj == "down":
        D, F = F, D
    T = 2048 * moe.top_k  # routed token copies of a 2048-token batch
    _compile(lambda x, e, w: gmm_ops.moe_apply(x, e, w), one_chip,
             ((T, D), jnp.bfloat16), ((T,), jnp.int32),
             ((moe.n_experts, D, F), jnp.bfloat16))


def test_ssd_scan_mamba2_2p7b(one_chip):
    cfg = get_config("mamba2-2.7b")
    ssm = cfg.ssm
    H, P, N = ssm.n_heads(cfg.d_model), ssm.head_dim, ssm.d_state
    S = 2048
    f32 = jnp.float32
    _compile(lambda x, dt, A, B, C: ssd_ops.ssd(x, dt, A, B, C,
                                                 chunk=ssm.chunk,
                                                 use_pallas=True),
             one_chip, ((1, S, H, P), f32), ((1, S, H), f32), ((H,), f32),
             ((1, S, 1, N), f32), ((1, S, 1, N), f32))


@pytest.mark.parametrize("hw", [(8192, 8192), (1001, 1000)])
def test_stencil(one_chip, hw):
    H, W = hw
    th = st_ops.tile_rows(H, W, 4)
    assert th % 8 == 0 and th * W * 4 * 8 <= st_ops.VMEM_BLOCK_BUDGET
    kern = jnp.ones((3, 3), jnp.float32) / 9.0
    _compile(lambda img: st_ops.stencil2d(img, kern), one_chip,
             ((H, W), jnp.float32))


@pytest.mark.parametrize("hw", [(4096, 4096), (1001, 1000)])
def test_mandelbrot(one_chip, hw):
    H, W = hw
    out = _compile(lambda: mb_ops.mandelbrot(H, W, pixel_delta=3.0 / W,
                                             max_iterations=1000), one_chip)
    assert out.out_info.shape == (H, W)
