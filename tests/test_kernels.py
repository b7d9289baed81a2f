"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpreted on CPU).

Every kernel gets (a) a parametrised sweep over shapes/dtypes and (b) a
hypothesis property test on the contract that matters (e.g. causality)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.mandelbrot import ops as mb_ops, ref as mb_ref
from repro.kernels.moe_gmm import ops as gmm_ops, ref as gmm_ref
from repro.kernels.ssd_scan import ops as ssd_ops, ref as ssd_ref
from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.kernels.stencil import ops as st_ops, ref as st_ref


# --------------------------------------------------------------------------
# stencil
# --------------------------------------------------------------------------

class TestStencil:
    @pytest.mark.parametrize("hw", [(64, 64), (100, 96), (33, 128), (8, 8)])
    @pytest.mark.parametrize("k", [3, 5])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_vs_ref(self, rng, hw, k, dtype):
        img = jnp.asarray(rng.normal(size=hw), dtype)
        kern = jnp.asarray(rng.normal(size=(k, k)).astype(np.float32))
        out = st_ops.stencil2d(img, kern, tile_h=32)
        refv = st_ref.stencil2d(img, kern)
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(refv, np.float32),
                                   rtol=tol, atol=tol)

    def test_identity_kernel(self, rng):
        img = jnp.asarray(rng.normal(size=(32, 32)).astype(np.float32))
        k = jnp.zeros((3, 3)).at[1, 1].set(1.0)
        out = st_ops.stencil2d(img, k)
        np.testing.assert_allclose(np.asarray(out), np.asarray(img),
                                   rtol=1e-6)


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

class TestFlashAttention:
    @pytest.mark.parametrize("shape", [
        (1, 4, 2, 64, 32),   # GQA
        (2, 8, 1, 96, 64),   # MQA
        (2, 4, 4, 128, 32),  # MHA
        (1, 2, 2, 33, 16),   # ragged seq (padding path)
    ])
    def test_causal_vs_ref(self, rng, shape):
        B, H, K, S, D = shape
        q = jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32)) * .3
        k = jnp.asarray(rng.normal(size=(B, K, S, D)).astype(np.float32)) * .3
        v = jnp.asarray(rng.normal(size=(B, K, S, D)).astype(np.float32))
        out = fa_ops.mha(q, k, v, causal=True, block_q=32, block_k=32)
        refv = fa_ref.mha(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(refv),
                                   rtol=2e-4, atol=2e-4)

    def test_decode_shape(self, rng):
        B, H, K, S, D = 2, 4, 2, 80, 32
        q = jnp.asarray(rng.normal(size=(B, H, 1, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, K, S, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, K, S, D)).astype(np.float32))
        out = fa_ops.mha(q, k, v, causal=True, block_q=32, block_k=32)
        refv = fa_ref.mha(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(refv),
                                   rtol=2e-4, atol=2e-4)

    def test_bf16(self, rng):
        B, H, K, S, D = 1, 2, 2, 64, 32
        q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(B, K, S, D)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(B, K, S, D)), jnp.bfloat16)
        out = fa_ops.mha(q, k, v, causal=True, block_q=32, block_k=32)
        refv = fa_ref.mha(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(refv, np.float32),
                                   rtol=5e-2, atol=5e-2)

    @pytest.mark.parametrize("shape", [
        (1, 4, 2, 64, 64, 16, 16), (2, 2, 1, 96, 96, 8, 32),
        (1, 2, 2, 40, 80, 16, 8)])
    def test_chunked_equals_dense(self, rng, shape):
        B, H, K, Sq, Sk, D, ck = shape
        q = jnp.asarray(rng.normal(size=(B, H, Sq, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, K, Sk, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, K, Sk, D)).astype(np.float32))
        a = fa_ref.mha(q, k, v, causal=True)
        b = fa_ref.mha_chunked(q, k, v, causal=True, chunk=ck)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)

    @settings(max_examples=10, deadline=None)
    @given(sq=st.integers(1, 40), extra=st.integers(0, 40))
    def test_causality_property(self, sq, extra):
        """Changing future keys never changes the output (the causal
        contract that the KV cache relies on)."""
        rng = np.random.default_rng(sq * 100 + extra)
        B, H, K, D = 1, 2, 1, 16
        sk = sq + extra
        q = jnp.asarray(rng.normal(size=(B, H, sq, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, K, sk, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, K, sk, D)).astype(np.float32))
        out1 = fa_ops.mha(q, k, v, causal=True, block_q=16, block_k=16)
        # perturb the last key/value (future of every query when extra>0)
        if extra > 0:
            k2 = k.at[:, :, -1].add(10.0)
            v2 = v.at[:, :, -1].add(10.0)
            out2 = fa_ops.mha(q[:, :, :-1] if False else q, k2, v2,
                              causal=True, block_q=16, block_k=16)
            np.testing.assert_allclose(np.asarray(out1[:, :, :sq - 1]),
                                       np.asarray(out2[:, :, :sq - 1]),
                                       rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# ssd scan
# --------------------------------------------------------------------------

class TestSSD:
    @pytest.mark.parametrize("shape", [
        (2, 32, 8, 4, 8), (3, 64, 16, 8, 16), (1, 48, 4, 4, 4)])
    def test_chunked_vs_naive(self, rng, shape):
        BH, S, P, N, chunk = shape
        x = jnp.asarray(rng.normal(size=(BH, S, P)).astype(np.float32))
        dt = jnp.asarray(rng.random((BH, S)).astype(np.float32)) * 0.1
        A = -jnp.asarray(rng.random(BH).astype(np.float32)) - 0.1
        B = jnp.asarray(rng.normal(size=(BH, S, N)).astype(np.float32)) * .3
        C = jnp.asarray(rng.normal(size=(BH, S, N)).astype(np.float32)) * .3
        y0, h0 = ssd_ref.ssd_naive(x, dt, A, B, C)
        y1, h1 = ssd_ref.ssd_chunked(x, dt, A, B, C, chunk=chunk)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(h0), np.asarray(h1),
                                   rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("chunk", [16, 32])
    def test_pallas_vs_naive(self, rng, chunk):
        BH, S, P, N = 2, 64, 8, 4
        x = jnp.asarray(rng.normal(size=(BH, S, P)).astype(np.float32))
        dt = jnp.asarray(rng.random((BH, S)).astype(np.float32)) * 0.1
        A = -jnp.asarray(rng.random(BH).astype(np.float32)) - 0.1
        B = jnp.asarray(rng.normal(size=(BH, S, N)).astype(np.float32)) * .3
        C = jnp.asarray(rng.normal(size=(BH, S, N)).astype(np.float32)) * .3
        y0, _ = ssd_ref.ssd_naive(x, dt, A, B, C)
        y1 = ssd_scan(x, dt, dt * A[:, None], B, C, chunk=chunk)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   rtol=1e-4, atol=1e-5)

    def test_decode_step_matches_scan(self, rng):
        """Recurrent decode reproduces the scan, step by step."""
        BH, S, P, N = 2, 12, 4, 4
        x = jnp.asarray(rng.normal(size=(BH, S, P)).astype(np.float32))
        dt = jnp.asarray(rng.random((BH, S)).astype(np.float32)) * 0.2
        A = -jnp.asarray(rng.random(BH).astype(np.float32)) - 0.1
        B = jnp.asarray(rng.normal(size=(BH, S, N)).astype(np.float32)) * .3
        C = jnp.asarray(rng.normal(size=(BH, S, N)).astype(np.float32)) * .3
        y_scan, _ = ssd_ref.ssd_naive(x, dt, A, B, C)
        h = jnp.zeros((BH, N, P))
        for t in range(S):
            y_t, h = ssd_ref.ssd_decode_step(h, x[:, t], dt[:, t], A,
                                             B[:, t], C[:, t])
            np.testing.assert_allclose(np.asarray(y_t),
                                       np.asarray(y_scan[:, t]),
                                       rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------------------
# mandelbrot
# --------------------------------------------------------------------------

class TestMandelbrot:
    @pytest.mark.parametrize("hw", [(64, 100), (40, 64), (8, 16)])
    def test_vs_ref(self, hw):
        H, W = hw
        # y0 chosen off the real axis: pixels with ci == 0 exactly sit on
        # the set boundary where an FMA-contraction ULP flips escape counts
        out = mb_ops.mandelbrot(H, W, x0=-2.0, y0=-1.0123,
                                pixel_delta=2.0 / W,
                                max_iterations=64)
        refv = mb_ref.mandelbrot(H, W, x0=-2.0, y0=-1.0123,
                                 pixel_delta=2.0 / W, max_iterations=64)
        same = np.asarray(out) == np.asarray(refv)
        assert same.mean() > 0.999, f"{(~same).sum()} boundary pixels differ"

    def test_interior_hits_escape_value(self):
        out = mb_ops.mandelbrot(64, 64, x0=-1.0, y0=-0.5,
                                pixel_delta=1.0 / 64, max_iterations=50)
        assert int((np.asarray(out) == 50).sum()) > 0  # interior present


# --------------------------------------------------------------------------
# moe grouped matmul
# --------------------------------------------------------------------------

class TestMoEGmm:
    @pytest.mark.parametrize("T,D,F,E,tile", [
        (64, 16, 32, 4, 16), (200, 32, 64, 8, 16), (33, 8, 16, 2, 8)])
    def test_vs_ref(self, rng, T, D, F, E, tile):
        x = jnp.asarray(rng.normal(size=(T, D)).astype(np.float32))
        eo = jnp.asarray(rng.integers(0, E, T))
        w = jnp.asarray(rng.normal(size=(E, D, F)).astype(np.float32)) * .1
        y = gmm_ops.moe_apply(x, eo, w, tile_m=tile, tile_f=16)
        refv = gmm_ref.gmm(x, eo, w)
        np.testing.assert_allclose(np.asarray(y), np.asarray(refv),
                                   rtol=1e-5, atol=1e-5)

    def test_skewed_routing(self, rng):
        """All tokens to one expert (worst-case padding path)."""
        T, D, F, E = 32, 8, 16, 4
        x = jnp.asarray(rng.normal(size=(T, D)).astype(np.float32))
        eo = jnp.full((T,), 2)
        w = jnp.asarray(rng.normal(size=(E, D, F)).astype(np.float32))
        y = gmm_ops.moe_apply(x, eo, w, tile_m=8, tile_f=16)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(x @ w[2]), rtol=1e-5,
                                   atol=1e-5)
