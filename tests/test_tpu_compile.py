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


def test_mandelbrot_farm_bands(one_chip):
    """The farm's worker stage at the cell's shape: 16 bands of 64 rows by
    4096 under ``vmap``, the top rows traced, 1000 iterations."""
    bands, rows, W = 16, 64, 4096
    out = _compile(jax.vmap(lambda r: mb_ops.mandelbrot_band(
        r, rows=rows, width=W, x0=-2.2, y0=-1.15, pixel_delta=3.0 / W,
        max_iterations=1000)), one_chip, ((bands,), jnp.int32))
    counts, ran = out.out_info
    assert counts.shape == (bands, rows, W)
    assert ran.shape == (bands, rows // 8, W // 512)


def test_chunked_attention_backward_deepseek_v2_lite(one_chip):
    """The training path's attention (XLA, query-chunked) at the DeepSeek
    cell's shape, differentiated: no whole-row ``reduce-window`` (the TPU
    compiler's form of a long masked softmax's max, which made a step some
    forty times slower), one chunk's probabilities at a time, and under the
    causal mask each chunk reads only the keys up to its diagonal (136 of
    the 256 key blocks of 16 chunks, with the softmax's elementwise work)."""
    from repro.kernels.flash_attention import ref as fa_ref
    m = get_config("deepseek-v2-lite").mla
    dk, dv = m.qk_nope_head_dim + m.qk_rope_head_dim, m.v_head_dim
    B, H, S = 2, 16, 8192
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in ((B, H, S, dk), (B, H, S, dk), (B, H, S, dv))]
    flops = {}
    for causal in (True, False):
        def loss(q, k, v):
            out = fa_ref.mha_chunked(q, k, v, causal=causal, chunk=512)
            return jnp.sum(out.astype(jnp.float32))

        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            *args).compile()
        assert "reduce-window" not in compiled.as_text()
        # all of (B, H, S, S) probabilities in float32 would be 8.6 GB
        assert compiled.memory_analysis().temp_size_in_bytes < 2e9
        flops[causal] = compiled.cost_analysis()["flops"]
    assert flops[True] <= 0.6 * flops[False], flops


def test_held_experts_backward_deepseek_v2_lite(one_chip):
    """The held-expert layer (8 of 64, top 6) over a 2 x 8192 batch,
    differentiated: the grouped matmuls compile to the TPU kernel."""
    from repro.models import moe
    cfg = get_config("deepseek-v2-lite")
    m = cfg.moe
    D, F = cfg.d_model, m.d_expert
    f32 = jnp.float32

    def loss(p, x):
        y, aux, _ = moe.moe_apply_grouped(p, cfg, x)
        return jnp.sum(y.astype(f32)) + aux

    sds = lambda s, d=f32: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    p = {"router": sds((D, m.n_experts)),
         "experts": {"gate": sds((m.n_held, D, F)),
                     "up": sds((m.n_held, D, F)),
                     "down": sds((m.n_held, F, D))},
         "shared": {"gate": sds((D, 2 * F)), "up": sds((D, 2 * F)),
                    "down": sds((2 * F, D))}}
    compiled = jax.jit(jax.grad(loss)).lower(
        p, sds((2, 8192, D), jnp.bfloat16)).compile()
    assert "tpu_custom_call" in compiled.as_text()
