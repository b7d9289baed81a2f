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

    @pytest.mark.parametrize("shape,causal", [
        pytest.param((1, 4, 2, 64, 64, 16, 16), True, id="shape0"),
        pytest.param((2, 2, 1, 96, 96, 8, 32), True, id="shape1"),
        pytest.param((1, 2, 2, 40, 80, 16, 8), True, id="shape2"),
        pytest.param((1, 2, 2, 40, 80, 16, 8), False, id="full_extent"),
        pytest.param((1, 2, 1, 32, 48, 16, 32), True, id="one_chunk")])
    def test_chunked_equals_dense(self, rng, shape, causal):
        B, H, K, Sq, Sk, D, ck = shape
        q = jnp.asarray(rng.normal(size=(B, H, Sq, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, K, Sk, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, K, Sk, D)).astype(np.float32))
        a = fa_ref.mha(q, k, v, causal=causal)
        b = fa_ref.mha_chunked(q, k, v, causal=causal, chunk=ck)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("fn", ["mha", "mha_chunked"])
    def test_value_head_dim_differs(self, rng, fn):
        """Latent attention: keys of 192 against values of 128, against the
        softmax written out."""
        B, H, S, Dk, Dv = 1, 2, 64, 192, 128
        q = jnp.asarray(rng.normal(size=(B, H, S, Dk)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, H, S, Dk)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, H, S, Dv)).astype(np.float32))
        kw = {"chunk": 16} if fn == "mha_chunked" else {}
        out = getattr(fa_ref, fn)(q, k, v, causal=True, scale=0.1, **kw)
        s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q, np.float64),
                      np.asarray(k, np.float64)) * 0.1
        s = np.where(np.tril(np.ones((S, S), bool)), s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("bhqk,bhkd->bhqd", p, np.asarray(v, np.float64))
        assert out.shape == (B, H, S, Dv)
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4,
                                   atol=1e-4)

    @pytest.mark.parametrize("dv,Sk,causal,chunk", [
        pytest.param(192, 64, True, 16, id="192"),
        pytest.param(128, 64, True, 16, id="128"),
        pytest.param(128, 96, True, 16, id="longer_keys"),
        pytest.param(128, 96, False, 16, id="full_extent"),
        pytest.param(128, 64, True, 64, id="one_chunk")])
    def test_chunked_grad_equals_dense(self, rng, dv, Sk, causal, chunk):
        """The rematerialised chunks, each over its own key extent, change
        nothing in the gradient."""
        B, H, K, S, D = 1, 4, 2, 64, 192
        q = jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, K, Sk, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, K, Sk, dv)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(B, H, S, dv)).astype(np.float32))

        def loss(f, **kw):
            return lambda q, k, v: jnp.sum(
                f(q, k, v, causal=causal, **kw) * w)

        a = jax.grad(loss(fa_ref.mha), argnums=(0, 1, 2))(q, k, v)
        b = jax.grad(loss(fa_ref.mha_chunked, chunk=chunk),
                     argnums=(0, 1, 2))(q, k, v)
        for x, y in zip(a, b):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-4, atol=1e-4)

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

def _harness_reference():
    """The farm cell's float32 reference (absolute rows), loaded by path."""
    import importlib.util
    from pathlib import Path
    path = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
            / "references" / "mandelbrot.py")
    spec = importlib.util.spec_from_file_location("mandelbrot_reference",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


mb_harness_ref = _harness_reference()

# bands as the farm renders them: (top row, rows, width, iters).  Widths off
# the 128-lane tiling, bands shorter than a tile, top rows on both sides of
# the image, iteration counts off the check block.  y0 is chosen so that no
# row's ci is exactly zero: there a pixel sits on the real axis, where an
# FMA contraction's one ulp flips escape counts
BANDS = [(0, 8, 32, 20), (-37, 5, 100, 150), (123, 13, 1000, 60),
         (-8, 64, 256, 200), (301, 3, 128, 128), (-64, 16, 640, 50),
         (40, 24, 384, 64)]
BAND_KW = dict(x0=-2.0, y0=-1.0123)


def _band(row0, rows, width, iters, **kw):
    kw = {**BAND_KW, "pixel_delta": 2.0 / width, **kw}
    counts, ran = mb_ops.mandelbrot_band(jnp.int32(row0), rows=rows,
                                         width=width, max_iterations=iters,
                                         **kw)
    want = mb_harness_ref.counts(
        jnp.asarray(row0 + np.arange(rows), jnp.int32), width, kw["x0"],
        kw["y0"], kw["pixel_delta"], iters)
    return np.asarray(counts), np.asarray(ran), np.asarray(want)


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
        assert same.all(), f"{(~same).sum()} pixels differ"

    def test_interior_hits_escape_value(self):
        out = mb_ops.mandelbrot(64, 64, x0=-1.0, y0=-0.5,
                                pixel_delta=1.0 / 64, max_iterations=50)
        assert int((np.asarray(out) == 50).sum()) > 0  # interior present

    @pytest.mark.parametrize("row0,rows,width,iters", BANDS)
    def test_band_vs_reference(self, row0, rows, width, iters):
        """The band kernel's counts equal the float32 reference's, bit for
        bit, whatever the tile a pixel lands in stopped at."""
        counts, _, want = _band(row0, rows, width, iters)
        assert counts.shape == want.shape == (rows, width)
        np.testing.assert_array_equal(counts, want)

    @pytest.mark.parametrize("row0,rows,width,iters", BANDS)
    def test_tile_iterations(self, row0, rows, width, iters):
        """Each tile runs to the first check at or after its slowest
        pixel's count: the checks come after the ``iters % CHECK_EVERY``
        odd steps and after each block of ``CHECK_EVERY`` from there, up to
        ``iters``.  Pad pixels never hold a tile back."""
        from repro.kernels.mandelbrot import kernel
        _, ran, want = _band(row0, rows, width, iters)
        tw, blk = kernel.tile_width(width), kernel.CHECK_EVERY
        th = kernel.TILE_H
        hp, wp = -(-rows // th) * th, -(-width // tw) * tw
        padded = np.zeros((hp, wp), np.int64)
        padded[:rows, :width] = want
        slowest = padded.reshape(hp // th, th, wp // tw, tw).max(axis=(1, 3))
        odd = iters % blk
        rule = odd + blk * np.maximum(-(-(slowest - odd) // blk), 0)
        assert rule.max() <= iters
        np.testing.assert_array_equal(ran, rule)

    @pytest.mark.parametrize("where", ["outside", "inside"])
    def test_whole_tile_stop(self, where):
        """A tile wholly outside the set stops at its first check; a tile
        wholly inside it runs every iteration."""
        from repro.kernels.mandelbrot import kernel
        iters = 200
        kw = (dict(x0=2.5, y0=0.5, pixel_delta=1e-3) if where == "outside"
              else dict(x0=-0.3, y0=0.05, pixel_delta=1e-4))
        counts, ran, want = _band(0, 16, 256, iters, **kw)
        np.testing.assert_array_equal(counts, want)
        if where == "outside":
            assert (counts == 1).all()
            first_check = iters % kernel.CHECK_EVERY or kernel.CHECK_EVERY
            assert (ran == first_check).all()
        else:
            assert (counts == iters).all() and (ran == iters).all()

    def test_band_under_vmap(self):
        """The farm renders its bands through ``vmap`` with the top row
        traced: each band equals the band rendered alone."""
        rows, width, iters = 8, 100, 50
        kw = dict(x0=-2.0, y0=-1.0123, pixel_delta=2.0 / width,
                  max_iterations=iters)
        tops = jnp.asarray([-40, -8, 0, 24, 77], jnp.int32)
        counts, ran = jax.vmap(lambda r: mb_ops.mandelbrot_band(
            r, rows=rows, width=width, **kw))(tops)
        for b, top in enumerate(np.asarray(tops)):
            one, one_ran = mb_ops.mandelbrot_band(top, rows=rows,
                                                  width=width, **kw)
            np.testing.assert_array_equal(counts[b], one)
            np.testing.assert_array_equal(ran[b], one_ran)


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
