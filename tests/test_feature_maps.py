"""Feature-map and FFT-family sketch tests.

Oracles (reference test strategy, SURVEY §4 + statistical regression
style):
- WHT/DCT: orthonormality + exact small-case identity.
- FJLT: norm preservation in expectation (JL property), JSON round-trip.
- RFT/QRFT/FastRFT: feature inner products approximate the kernel
  (Gaussian/Laplacian/Matérn), statistical tolerance.
- RLT: approximates the exponential semigroup kernel on histograms.
- PPT: approximates the polynomial kernel.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from libskylark_tpu import SketchContext
from libskylark_tpu.sketch import (
    FJLT,
    PPT,
    RFUT,
    ExpSemigroupQRLT,
    ExpSemigroupRLT,
    FastGaussianRFT,
    FastMaternRFT,
    GaussianQRFT,
    GaussianRFT,
    LaplacianQRFT,
    LaplacianRFT,
    MaternRFT,
    dct,
    from_json,
    wht,
)


class TestWHT:
    def test_matches_dense_hadamard(self, rng):
        for n in (2, 8, 64, 512):
            H = np.array([[1.0]])
            while H.shape[0] < n:
                H = np.block([[H, H], [H, -H]])
            x = rng.standard_normal((n, 3))
            np.testing.assert_allclose(
                np.asarray(wht(jnp.asarray(x), axis=0)),
                H @ x / np.sqrt(n),
                rtol=1e-10,
                atol=1e-12,
            )

    def test_orthonormal(self, rng):
        x = jnp.asarray(rng.standard_normal((128, 5)))
        y = wht(x, axis=0)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(y), axis=0),
            np.linalg.norm(np.asarray(x), axis=0),
            rtol=1e-12,
        )
        np.testing.assert_allclose(
            np.asarray(wht(y, axis=0)), np.asarray(x), atol=1e-10
        )

    def test_axis1(self, rng):
        x = jnp.asarray(rng.standard_normal((3, 16)))
        np.testing.assert_allclose(
            np.asarray(wht(x, axis=1)),
            np.asarray(wht(x.T, axis=0)).T,
            rtol=1e-12,
        )

    def test_non_pow2_raises(self, rng):
        with pytest.raises(ValueError, match="power-of-2"):
            wht(jnp.ones((12, 2)))


class TestDCT:
    def test_orthonormal(self, rng):
        x = jnp.asarray(rng.standard_normal((60, 4)))
        y = dct(x, axis=0)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(y), axis=0),
            np.linalg.norm(np.asarray(x), axis=0),
            rtol=1e-10,
        )


class TestRFUT:
    def test_norm_preserving(self, rng):
        x = jnp.asarray(rng.standard_normal((100, 7)))
        T = RFUT(100, SketchContext(seed=5), fut="wht")
        y = T.apply(x, "columnwise")
        assert y.shape == (128, 7)  # padded to pow2
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(y), axis=0),
            np.linalg.norm(np.asarray(x), axis=0),
            rtol=1e-10,
        )

    @pytest.mark.slow
    def test_dct_exact_size(self, rng):
        x = jnp.asarray(rng.standard_normal((60, 3)))
        T = RFUT(60, SketchContext(seed=6), fut="dct")
        assert T.apply(x, "columnwise").shape == (60, 3)


class TestFJLT:
    @pytest.mark.slow
    @pytest.mark.parametrize("fut", ["wht", "dct"])
    def test_norm_preservation_statistical(self, rng, fut):
        n, s, m = 200, 64, 5
        X = jnp.asarray(rng.standard_normal((n, m)))
        norms = np.linalg.norm(np.asarray(X), axis=0)
        errs = []
        for rep in range(5):
            S = FJLT(n, s, SketchContext(seed=rep), fut=fut)
            SX = S.apply(X, "columnwise")
            errs.append(np.abs(np.linalg.norm(np.asarray(SX), axis=0) - norms) / norms)
        # average relative norm distortion ~ 1/sqrt(s); allow 3x slack
        assert np.mean(errs) < 3.0 / np.sqrt(s)

    @pytest.mark.slow
    def test_rowwise_consistent(self, rng):
        n, s = 100, 32
        X = jnp.asarray(rng.standard_normal((4, n)))
        S = FJLT(n, s, SketchContext(seed=3))
        R1 = S.apply(X, "rowwise")
        S2 = FJLT(n, s, SketchContext(seed=3))
        R2 = S2.apply(X.T, "columnwise").T
        np.testing.assert_allclose(np.asarray(R1), np.asarray(R2), rtol=1e-10)

    @pytest.mark.slow
    def test_json_roundtrip(self, rng):
        S = FJLT(50, 16, SketchContext(seed=9))
        S2 = from_json(S.to_json())
        X = jnp.asarray(rng.standard_normal((50, 2)))
        np.testing.assert_array_equal(
            np.asarray(S.apply(X, "columnwise")),
            np.asarray(S2.apply(X, "columnwise")),
        )


class TestFJLTSrhtGemm:
    """The subsampled-Hadamard-as-matmul path must produce the SAME
    transform as the streamed WHT + gather (same samples, same diagonal;
    only the evaluation order differs)."""

    @pytest.mark.parametrize(
        "dim,shape", [("rowwise", (8, 300)), ("columnwise", (300, 8))]
    )
    @pytest.mark.slow
    def test_matches_wht_gather(self, rng, monkeypatch, dim, shape):
        n, s = 300, 32
        A = jnp.asarray(rng.standard_normal(shape))
        S = FJLT(n, s, SketchContext(seed=17))
        monkeypatch.setenv("SKYLARK_NO_SRHT_GEMM", "1")
        ref = S.apply(A, dim)  # streamed WHT + gather
        monkeypatch.delenv("SKYLARK_NO_SRHT_GEMM")
        monkeypatch.setattr(FJLT, "_gemm_wins", lambda self, dtype: True)
        out = S.apply(A, dim)
        assert out.dtype == A.dtype
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-10, atol=1e-10
        )

    def test_pow2_n_no_padding(self, rng, monkeypatch):
        n, s = 256, 64
        A = jnp.asarray(rng.standard_normal((4, n)))
        S = FJLT(n, s, SketchContext(seed=23))
        monkeypatch.setenv("SKYLARK_NO_SRHT_GEMM", "1")
        ref = S.apply(A, "rowwise")
        monkeypatch.delenv("SKYLARK_NO_SRHT_GEMM")
        out = S._apply_srht_gemm(A, rowwise=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-10, atol=1e-10
        )

    def test_gate(self, monkeypatch):
        ctx = SketchContext(seed=1)
        # measured configs from BASELINE.md (n=4096):
        assert FJLT(4096, 256, ctx)._gemm_wins(jnp.float32)
        # f32 s=1024 now WINS via the 3-pass bf16 split (round-2 fix of
        # the documented large-S f32 gather bottleneck)
        assert FJLT(4096, 1024, ctx)._gemm_wins(jnp.float32)
        assert FJLT(4096, 1024, ctx)._gemm_wins(jnp.bfloat16)
        # huge S: matmul flops dominate → streamed path
        assert not FJLT(4096, 4096, ctx)._gemm_wins(jnp.float32)
        # f64 keeps the exact-matmul gate (CPU parity runs): tighter
        # crossover than the f32 split (fpb 80 vs 500/3 per pass)
        assert not FJLT(4096, 2048, ctx)._gemm_wins(jnp.float64)
        # element cap (ADVICE r1): a huge realized G must not transiently
        # blow HBM even when the flops gate would fire (large-n small-S
        # columnwise case)
        assert not FJLT(1 << 20, 128, ctx)._gemm_wins(jnp.bfloat16)
        monkeypatch.setenv("SKYLARK_NO_SRHT_GEMM", "1")
        assert not FJLT(4096, 128, ctx)._gemm_wins(jnp.float32)

    def test_f32_split_accuracy(self, rng, monkeypatch):
        """The 3-pass bf16 split reproduces the f32 WHT+gather transform
        to f32-accumulation accuracy (the split itself is exact to ~24
        mantissa bits; only summation order differs)."""
        import jax

        n, s = 512, 128
        A32 = jnp.asarray(rng.standard_normal((16, n)), jnp.float32)
        S = FJLT(n, s, SketchContext(seed=71))
        monkeypatch.setenv("SKYLARK_NO_SRHT_GEMM", "1")
        ref = S.apply(A32, "rowwise")
        monkeypatch.delenv("SKYLARK_NO_SRHT_GEMM")
        out = S._apply_srht_gemm(A32, rowwise=True)
        assert out.dtype == jnp.float32
        scale = float(jnp.linalg.norm(A32) / np.sqrt(s))
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5,
            atol=2e-5 * scale,
        )


def _kernel_mse(Z, K):
    """Mean abs error between feature inner products and kernel matrix."""
    G = np.asarray(Z.T @ Z)
    return np.mean(np.abs(G - K))


def _gaussian_K(X, sigma):
    D2 = ((X[:, None, :] - X[None, :, :]) ** 2).sum(-1)
    return np.exp(-D2 / (2 * sigma**2))


def _laplacian_K(X, sigma):
    D1 = np.abs(X[:, None, :] - X[None, :, :]).sum(-1)
    return np.exp(-D1 / sigma)


class TestRFT:
    @pytest.mark.slow
    def test_gaussian_kernel_approx(self, rng):
        d, m, s, sigma = 10, 20, 4096, 2.0
        X = rng.standard_normal((m, d))
        K = _gaussian_K(X, sigma)
        F = GaussianRFT(d, s, SketchContext(seed=1), sigma=sigma)
        Z = F.apply(jnp.asarray(X.T), "columnwise")  # (s, m)
        assert _kernel_mse(Z, K) < 0.05

    @pytest.mark.slow
    def test_laplacian_kernel_approx(self, rng):
        d, m, s, sigma = 8, 20, 8192, 3.0
        X = rng.standard_normal((m, d))
        K = _laplacian_K(X, sigma)
        F = LaplacianRFT(d, s, SketchContext(seed=2), sigma=sigma)
        Z = F.apply(jnp.asarray(X.T), "columnwise")
        assert _kernel_mse(Z, K) < 0.08

    @pytest.mark.slow
    def test_matern_features_finite_and_shaped(self, rng):
        F = MaternRFT(6, 512, SketchContext(seed=3), nu=1.5, l=2.0)
        Z = F.apply(jnp.asarray(rng.standard_normal((6, 9))), "columnwise")
        assert Z.shape == (512, 9)
        assert np.all(np.isfinite(np.asarray(Z)))
        with pytest.raises(ValueError, match="2\\*nu"):
            MaternRFT(6, 64, SketchContext(seed=4), nu=0.7)

    @pytest.mark.slow
    def test_rowwise_matches_columnwise(self, rng):
        d, s = 7, 128
        X = rng.standard_normal((5, d))
        F1 = GaussianRFT(d, s, SketchContext(seed=5), sigma=1.5)
        F2 = GaussianRFT(d, s, SketchContext(seed=5), sigma=1.5)
        np.testing.assert_allclose(
            np.asarray(F1.apply(jnp.asarray(X), "rowwise")),
            np.asarray(F2.apply(jnp.asarray(X.T), "columnwise")).T,
            rtol=1e-6, atol=1e-8,
        )

    def test_json_roundtrip(self, rng):
        F = GaussianRFT(5, 64, SketchContext(seed=6), sigma=0.7)
        F2 = from_json(F.to_json())
        X = jnp.asarray(rng.standard_normal((5, 3)))
        np.testing.assert_array_equal(
            np.asarray(F.apply(X, "columnwise")),
            np.asarray(F2.apply(X, "columnwise")),
        )


_RFT_MAPS = [
    pytest.param(GaussianRFT, {"sigma": 0.5}, id="gaussian"),
    pytest.param(LaplacianRFT, {"sigma": 40.0}, id="laplacian"),
    pytest.param(MaternRFT, {"nu": 1.5, "l": 0.6}, id="matern"),
]
_DIMS = ["rowwise", "columnwise"]


def _bf16_case(cls, kw, dim, rng, n=24, s=64, m=48):
    """A map, a bfloat16 operand in ``dim``'s layout, and the float64
    phase ``scales·(X_bf16·W_bf16ᵀ) + shifts`` as (m, s), entries with
    |phase| > 64 masked out (Cauchy rows have no bound)."""
    F = cls(n, s, SketchContext(seed=11), **kw)
    X = jnp.asarray(rng.standard_normal((m, n)), jnp.bfloat16)
    W = np.asarray(F._underlying.realize(jnp.bfloat16), np.float64)
    phase = np.asarray(X, np.float64) @ W.T
    scales = F.scales(jnp.float32)
    if scales is not None:
        phase = phase * np.asarray(scales, np.float64)
    phase = phase + np.asarray(F.shifts(jnp.float32), np.float64)
    keep = np.abs(phase) <= 64.0
    assert keep.mean() > 0.9 and np.abs(phase[keep]).max() > 16.0
    A = X if dim == "rowwise" else X.T
    return F, A, phase, keep


class TestRFTEpilogue:
    """The epilogue by operand dtype (``rft._epilogue_kernel``): narrow
    operands take the phase from the f32 accumulator, in turns, through
    a polynomial cosine; f32/f64 keep ``outscale·cos(WX + shifts)``."""

    @pytest.mark.parametrize("dim", _DIMS)
    @pytest.mark.parametrize("cls,kw", _RFT_MAPS)
    def test_bf16_against_float64(self, cls, kw, dim, rng):
        from libskylark_tpu.sketch import rft

        F, A, phase, keep = _bf16_case(cls, kw, dim, rng)
        want = np.cos(phase)  # features over outscale
        # before the last rounding: the f32 chain on the f32 accumulator
        acc = F._underlying.apply(A, dim)
        assert acc.dtype == jnp.float32
        shifts, scales = F._turns()
        if dim == "columnwise":
            acc = acc.T
        u = acc * (rft._INV_TWO_PI if scales is None else scales) + shifts
        got = np.asarray(jax.jit(rft._cos_turns)(u.astype(jnp.float32)), np.float64)
        assert np.abs(got - want)[keep].max() < 1e-5
        # after it: the nearest bfloat16 of the float64 feature, but for ties
        Z = F.apply(A, dim)
        assert Z.dtype == jnp.bfloat16
        Z = np.asarray(Z.astype(jnp.float32), np.float64)
        Z = Z if dim == "rowwise" else Z.T
        want = F.outscale * want
        nearest = np.asarray(
            jnp.asarray(want, jnp.float32).astype(jnp.bfloat16).astype(jnp.float32),
            np.float64,
        )
        off = (Z != nearest) & keep
        assert off.mean() < 0.01
        slack = 1e-5 * F.outscale
        assert np.all(np.abs(Z - want)[off] <= np.abs(nearest - want)[off] + slack)

    @pytest.mark.parametrize("dim", _DIMS)
    @pytest.mark.parametrize("cls,kw", _RFT_MAPS)
    def test_bf16_routes_bitwise(self, cls, kw, dim, rng):
        """eager ≡ planned ≡ in a caller's jit ≡ apply_with_operands ≡
        slice-and-finalize, to the bit, in bfloat16."""
        from libskylark_tpu import plans

        F, A, _, _ = _bf16_case(cls, kw, dim, rng)
        eager = np.asarray(F.apply(A, dim))
        routes = {
            "planned": plans.apply(F, A, dim),
            "jit": jax.jit(lambda A_: F.apply(A_, dim))(A),
            "operands": F.apply_with_operands(
                F.hoistable_operands(jnp.bfloat16), A, dim
            ),
        }
        if dim == "rowwise":  # finished blocks, concatenated
            routes["slices"] = jnp.concatenate(
                [F.apply_slice(A[i : i + 16], i, dim) for i in range(0, 48, 16)]
            )
        else:  # the f32 accumulator, then one epilogue
            part = F.apply_slice(A, 0, dim)
            assert part.dtype == jnp.float32
            routes["slices"] = F.finalize_slices(part, dim, jnp.bfloat16)
            part = jax.jit(F.apply_slice_kernel)(A, jnp.int32(0))
            routes["slice_kernel"] = F.finalize_slices(part, dim, jnp.bfloat16)
        for name, got in routes.items():
            assert got.dtype == jnp.bfloat16, name
            np.testing.assert_array_equal(np.asarray(got), eager, err_msg=name)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
    @pytest.mark.parametrize("cls,kw", _RFT_MAPS)
    def test_f32_f64_keep_xla_cosine(self, cls, kw, dtype, rng):
        """f32 and f64 features are bit for bit ``outscale·cos(scales·WX +
        shifts)`` (what ``krr_faster_mnist_pcg``'s preconditioner reads)."""
        n, s, m = 24, 64, 48
        F = cls(n, s, SketchContext(seed=11), **kw)
        X = jnp.asarray(rng.standard_normal((m, n)), dtype)
        shifts, scales = F.shifts(dtype), F.scales(dtype)
        outscale = jnp.asarray(F.outscale, dtype)

        @jax.jit
        def written_out(WX, shifts, scales):
            if scales is not None:
                WX = WX * scales
            return outscale * jnp.cos(WX + shifts)

        WX = F._underlying.apply(X, "rowwise")
        assert WX.dtype == dtype
        got = F.apply(X, "rowwise")
        assert got.dtype == dtype
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(written_out(WX, shifts, scales))
        )
        WX = F._underlying.apply(X.T, "columnwise")
        want = np.asarray(written_out(
            WX, shifts[:, None], None if scales is None else scales[:, None]))
        np.testing.assert_array_equal(np.asarray(F.apply(X.T, "columnwise")), want)
        np.testing.assert_array_equal(
            np.asarray(F.finalize_slices(F.apply_slice(X.T, 0))), want
        )

    def test_linear_sketches_keep_their_dtype(self, rng):
        """Only the feature maps' underlying product is widened."""
        from libskylark_tpu.sketch import CT, JLT

        A = jnp.asarray(rng.standard_normal((24, 5)), jnp.bfloat16)
        for S in (JLT(24, 8, SketchContext(seed=1)), CT(24, 8, SketchContext(seed=1))):
            assert S.apply(A, "columnwise").dtype == jnp.bfloat16
            assert S.apply(A.T, "rowwise").dtype == jnp.bfloat16
            assert S.apply_slice(A[:7], 0).dtype == jnp.bfloat16

    def test_qrft_shares_the_epilogue(self, rng):
        X = rng.standard_normal((9, 5))
        F = GaussianQRFT(5, 64, SketchContext(seed=1), sigma=1.5, skip=50)
        W, shifts = F.realize(jnp.float64)
        want = F.outscale * np.cos(X @ np.asarray(W).T + np.asarray(shifts))
        np.testing.assert_allclose(
            np.asarray(F.apply(jnp.asarray(X), "rowwise")), want, atol=1e-12
        )
        # bfloat16 (the Cauchy inverse CDF takes it; ndtri does not):
        # W and the shifts rounded to bfloat16, the phase kept in f32
        F = LaplacianQRFT(5, 64, SketchContext(seed=1), sigma=30.0, skip=50)
        Xb = jnp.asarray(X, jnp.bfloat16)
        W, shifts = F.realize(jnp.bfloat16)
        phase = np.asarray(Xb, np.float64) @ np.asarray(W, np.float64).T
        phase = phase + np.asarray(shifts, np.float64)
        keep = np.abs(phase) <= 64.0
        assert keep.mean() > 0.9
        Zb = F.apply(Xb, "rowwise")
        assert Zb.dtype == jnp.bfloat16
        err = np.abs(np.asarray(Zb, np.float64) - F.outscale * np.cos(phase))
        assert err[keep].max() < 2.0**-8 * F.outscale
        np.testing.assert_array_equal(
            np.asarray(Zb), np.asarray(F.apply(Xb.T, "columnwise")).T
        )


def _rows_of(start, rows, X):
    return jax.lax.dynamic_slice_in_dim(X, start, rows, axis=0)


class TestChunkProgramEpilogue:
    """What the streamed trainer's three programs lower to, by feature
    dtype (counted in the StableHLO text; no device needed)."""

    D, SZ, NB, BR, T = 16, 32, 2, 64, 3

    def _texts(self, dtype):
        from libskylark_tpu.ml import GaussianKernel
        from libskylark_tpu.ml.krr import streaming_krr_chunk_programs

        maps = [GaussianKernel(self.D, sigma=4.0).create_rft(
            self.SZ, "regular", SketchContext(seed=9))]

        gram, zr, apply_delta = streaming_krr_chunk_programs(
            maps, 0, self.NB, self.BR, _rows_of, dtype
        )
        lam = jnp.float32(0.1)
        X = jnp.zeros((self.NB * self.BR, self.D), dtype)
        R = jnp.zeros((self.NB, self.BR, self.T), jnp.float32)
        W = jnp.zeros((self.SZ, self.T), jnp.float32)
        return {
            "gram": gram.lower(lam, X).as_text(),
            "zr": zr.lower(lam, R, W, X).as_text(),
            "apply_delta": apply_delta.lower(R, W, X).as_text(),
        }

    @pytest.fixture(scope="class")
    def texts(self):
        return {"bfloat16": self._texts(jnp.bfloat16),
                "float32": self._texts(jnp.float32)}

    @pytest.mark.parametrize("program", ["gram", "zr", "apply_delta"])
    def test_bf16_features_hold_no_cosine(self, texts, program):
        text = texts["bfloat16"][program]
        panel = f"tensor<{self.BR}x{self.SZ}x"
        # (W's Box-Muller draw has a cosine of its own, at W's shape)
        assert not re.search(rf"stablehlo\.cosine .*{panel}", text)
        # the feature product hands over its f32 accumulator ...
        assert re.search(
            rf"stablehlo\.dot_general .*\(tensor<{self.BR}x{self.D}xbf16>, "
            rf"tensor<{self.SZ}x{self.D}xbf16>\) -> {panel}f32>", text)
        # ... and the epilogue rounds once, at its end
        body = text[text.index("func.func private @_epilogue_kernel"):]
        body = body[: body.index("\n  }")]
        assert body.startswith(
            f"func.func private @_epilogue_kernel(%arg0: {panel}f32>")
        to_bf16 = [ln for ln in body.splitlines() if f"-> {panel}bf16>" in ln]
        assert len(to_bf16) == 2  # the signature and the last convert
        assert "stablehlo.convert" in body.splitlines()[-2]
        assert "return" in body.splitlines()[-1]

    # crc32 of each program's StableHLO text at commit 0f41937 (x64 on,
    # as the suite runs): PR 39's trainer counts its passes on the host
    PARENT_TEXT = {
        "bfloat16": {"gram": 392854805, "zr": 222862983, "apply_delta": 283477666},
        "float32": {"gram": 1146523471, "zr": 324745983, "apply_delta": 2178169857},
    }

    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    @pytest.mark.parametrize("program", ["gram", "zr", "apply_delta"])
    def test_gaussian_programs_lower_to_the_parents_text(self, texts, program, dtype):
        import zlib

        got = zlib.crc32(texts[dtype][program].encode())
        assert got == self.PARENT_TEXT[dtype][program]

    @pytest.mark.parametrize("program", ["gram", "zr", "apply_delta"])
    def test_f32_features_hold_one_cosine(self, texts, program):
        text = texts["float32"][program]
        panel = f"tensor<{self.BR}x{self.SZ}x"
        assert len(re.findall(rf"stablehlo\.cosine .*{panel}f32>", text)) == 1
        assert "stablehlo.floor" not in text


class TestQRFT:
    @pytest.mark.slow
    def test_gaussian_kernel_approx_qmc(self, rng):
        # QMC should beat plain MC at equal S (or at least match).
        d, m, s, sigma = 6, 15, 1024, 2.0
        X = rng.standard_normal((m, d))
        K = _gaussian_K(X, sigma)
        F = GaussianQRFT(d, s, SketchContext(seed=1), sigma=sigma, skip=1000)
        Z = F.apply(jnp.asarray(X.T), "columnwise")
        assert _kernel_mse(Z, K) < 0.05

    def test_laplacian_qrft_finite(self, rng):
        F = LaplacianQRFT(5, 256, SketchContext(seed=2), sigma=1.0, skip=100)
        Z = F.apply(jnp.asarray(rng.standard_normal((5, 4))), "columnwise")
        assert np.all(np.isfinite(np.asarray(Z)))

    @pytest.mark.slow
    def test_deterministic_in_skip(self, rng):
        X = jnp.asarray(rng.standard_normal((5, 3)))
        Z1 = GaussianQRFT(5, 64, SketchContext(seed=1), skip=7).apply(X)
        Z2 = GaussianQRFT(5, 64, SketchContext(seed=99), skip=7).apply(X)
        np.testing.assert_array_equal(np.asarray(Z1), np.asarray(Z2))


class TestFastRFT:
    def test_gaussian_kernel_approx(self, rng):
        d, m, s, sigma = 16, 15, 4096, 2.0
        X = rng.standard_normal((m, d))
        K = _gaussian_K(X, sigma)
        F = FastGaussianRFT(d, s, SketchContext(seed=1), sigma=sigma)
        Z = F.apply(jnp.asarray(X.T), "columnwise")
        assert _kernel_mse(Z, K) < 0.06

    @pytest.mark.slow
    def test_matern_finite(self, rng):
        F = FastMaternRFT(10, 256, SketchContext(seed=2), nu=1.0, l=1.5)
        Z = F.apply(jnp.asarray(rng.standard_normal((10, 6))), "columnwise")
        assert Z.shape == (256, 6)
        assert np.all(np.isfinite(np.asarray(Z)))

    def test_rowwise_matches_columnwise(self, rng):
        d, s = 12, 128
        X = rng.standard_normal((4, d))
        F1 = FastGaussianRFT(d, s, SketchContext(seed=3), sigma=1.0)
        F2 = FastGaussianRFT(d, s, SketchContext(seed=3), sigma=1.0)
        np.testing.assert_allclose(
            np.asarray(F1.apply(jnp.asarray(X), "rowwise")),
            np.asarray(F2.apply(jnp.asarray(X.T), "columnwise")).T,
            rtol=1e-6, atol=1e-8,
        )

    def test_json_roundtrip(self, rng):
        F = FastGaussianRFT(9, 64, SketchContext(seed=4), sigma=1.2)
        F2 = from_json(F.to_json())
        X = jnp.asarray(rng.standard_normal((9, 2)))
        np.testing.assert_array_equal(
            np.asarray(F.apply(X, "columnwise")),
            np.asarray(F2.apply(X, "columnwise")),
        )

    @pytest.mark.parametrize("dim", ["rowwise", "columnwise"])
    @pytest.mark.parametrize(
        "cls,kw",
        [(FastGaussianRFT, {"sigma": 1.7}), (FastMaternRFT, {"nu": 1.5, "l": 0.9})],
    )
    def test_realized_matches_streaming(self, rng, monkeypatch, cls, kw, dim):
        """The realized-W MXU path (big bf16/f32 batches) must agree with
        the exact streaming form to the 4-pass split's ~2^-16-relative
        pre-cos bound (sketch/frft.py round-3 fast path)."""
        n, s, m = 24, 64, 128  # nb=32; batch >= 4*nb fires the gate
        A = rng.standard_normal((m, n)).astype(np.float32)
        arr = jnp.asarray(A if dim == "rowwise" else A.T)
        S = cls(n, s, SketchContext(seed=11), **kw)
        batch = m
        monkeypatch.setenv("SKYLARK_FRFT_GEMM", "1")  # CPU: force TPU path
        assert S._realize_wins(jnp.float32, batch)
        Z_fast = S.apply(arr, dim)
        monkeypatch.setenv("SKYLARK_NO_FRFT_GEMM", "1")
        assert not S._realize_wins(jnp.float32, batch)
        Z_exact = S.apply(arr, dim)
        np.testing.assert_allclose(
            np.asarray(Z_fast), np.asarray(Z_exact), atol=5e-4
        )

    def test_hoistable_operands_parity(self, rng):
        """apply_with_operands(hoistable_operands(dtype), A) must equal
        apply(A) bit-for-bit — streaming consumers hoist the W
        realization out of their panel loops (XLA does not LICM it)."""
        from libskylark_tpu.sketch.rft import GaussianRFT, MaternRFT

        for cls, kw in (
            (GaussianRFT, {"sigma": 1.7}),
            (MaternRFT, {"nu": 1.5, "l": 0.9}),
        ):
            n, s, m = 24, 32, 8
            F = cls(n, s, SketchContext(seed=17), **kw)
            A = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
            ops = F.hoistable_operands(jnp.float32)
            assert ops is not None
            np.testing.assert_array_equal(
                np.asarray(F.apply_with_operands(ops, A, "rowwise")),
                np.asarray(F.apply(A, "rowwise")),
            )
            np.testing.assert_array_equal(
                np.asarray(F.apply_with_operands(ops, A.T, "columnwise")),
                np.asarray(F.apply(A.T, "columnwise")),
            )
            # None ops / default transforms fall back to plain apply
            np.testing.assert_array_equal(
                np.asarray(F.apply_with_operands(None, A, "rowwise")),
                np.asarray(F.apply(A, "rowwise")),
            )
            # apply's input coercion carries over (review regression:
            # int inputs must not truncate W / run an int epilogue)
            Ai = np.arange(m * n).reshape(m, n) % 5
            np.testing.assert_array_equal(
                np.asarray(F.apply_with_operands(ops, Ai, "rowwise")),
                np.asarray(F.apply(Ai, "rowwise")),
            )

    def test_hoistable_operands_fastrft(self, rng, monkeypatch):
        """FastRFT hoisting: (realized W, shifts) — matches the forced
        realized apply exactly, and the streaming-KRR 'fast' tag path
        gets the same loop-hoisting as plain RFT."""
        from libskylark_tpu.sketch import FastGaussianRFT

        n, s, m = 24, 64, 160
        F = FastGaussianRFT(n, s, SketchContext(seed=19), sigma=2.0)
        A = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
        ops = F.hoistable_operands(jnp.float32)
        assert ops is not None and len(ops) == 2
        monkeypatch.setenv("SKYLARK_FRFT_GEMM", "1")
        assert F._realize_wins(jnp.float32, m)
        ref = F.apply(A, "rowwise")  # realized path
        np.testing.assert_array_equal(
            np.asarray(F.apply_with_operands(ops, A, "rowwise")),
            np.asarray(ref),
        )
        assert F.hoistable_operands(jnp.float64) is None

    def test_realized_gate_bounds(self, monkeypatch):
        S = FastGaussianRFT(24, 64, SketchContext(seed=12), sigma=1.0)
        assert not S._realize_wins(jnp.float32, 10_000)  # CPU backend: off
        monkeypatch.setenv("SKYLARK_FRFT_GEMM", "1")
        assert not S._realize_wins(jnp.float64, 10_000)  # f64 stays exact
        assert not S._realize_wins(jnp.float32, 64)      # small batch
        big = FastGaussianRFT(
            1 << 13, 1 << 14, SketchContext(seed=13), sigma=1.0
        )
        assert big.numblks * big._nb * big._nb > (64 << 20)
        assert not big._realize_wins(jnp.float32, 1 << 20)  # W cap


class TestRLT:
    @pytest.mark.slow
    def test_expsemigroup_kernel_approx(self, rng):
        # k(x,y) = exp(-beta * sum_i sqrt(x_i + y_i)) on histograms.
        d, m, s, beta = 5, 12, 16384, 0.3
        X = rng.random((m, d))  # non-negative
        K = np.exp(
            -beta * np.sqrt(X[:, None, :] + X[None, :, :]).sum(-1)
        )
        F = ExpSemigroupRLT(d, s, SketchContext(seed=1), beta=beta)
        Z = F.apply(jnp.asarray(X.T), "columnwise")
        assert _kernel_mse(Z, K) < 0.05

    @pytest.mark.slow
    def test_qrlt_finite_and_kernel(self, rng):
        d, m, s, beta = 4, 10, 4096, 0.25
        X = rng.random((m, d))
        K = np.exp(-beta * np.sqrt(X[:, None, :] + X[None, :, :]).sum(-1))
        F = ExpSemigroupQRLT(d, s, SketchContext(seed=2), beta=beta, skip=500)
        Z = F.apply(jnp.asarray(X.T), "columnwise")
        assert np.all(np.isfinite(np.asarray(Z)))
        assert _kernel_mse(Z, K) < 0.1


class TestPPT:
    def test_polynomial_kernel_approx(self, rng):
        d, m, s = 10, 15, 8192
        q, c, gamma = 2, 1.0, 0.5
        X = rng.standard_normal((m, d)) / np.sqrt(d)
        K = (gamma * (X @ X.T) + c) ** q
        F = PPT(d, s, SketchContext(seed=1), q=q, c=c, gamma=gamma)
        Z = F.apply(jnp.asarray(X.T), "columnwise")
        assert _kernel_mse(Z, K) < 0.05

    def test_exact_expectation_q1(self, rng):
        # q=1: CWT preserves inner products exactly in expectation; with
        # the constant term the feature map satisfies E[<z(x),z(y)>] =
        # gamma x.y + c. Sanity-check one draw loosely.
        d, s = 8, 4096
        x = rng.standard_normal(d)
        y = rng.standard_normal(d)
        F = PPT(d, s, SketchContext(seed=3), q=1, c=2.0, gamma=1.5)
        zx = np.asarray(F.apply(jnp.asarray(x), "columnwise"))
        zy = np.asarray(F.apply(jnp.asarray(y), "columnwise"))
        expected = 1.5 * float(x @ y) + 2.0
        assert abs(zx @ zy - expected) < 0.7

    def test_json_roundtrip(self, rng):
        F = PPT(6, 32, SketchContext(seed=4), q=3, c=0.5, gamma=2.0)
        F2 = from_json(F.to_json())
        X = jnp.asarray(rng.standard_normal((6, 3)))
        np.testing.assert_allclose(
            np.asarray(F.apply(X, "columnwise")),
            np.asarray(F2.apply(X, "columnwise")),
            rtol=1e-10,
        )

    def test_jittable(self, rng):
        F = PPT(6, 64, SketchContext(seed=5), q=2)
        Z = jax.jit(lambda X: F.apply(X, "columnwise"))(
            jnp.asarray(rng.standard_normal((6, 4)))
        )
        assert Z.shape == (64, 4)

    @pytest.mark.parametrize("dim", ["columnwise", "rowwise"])
    @pytest.mark.parametrize("q", [1, 2, 3])
    @pytest.mark.parametrize("s", [16, 64, 15])
    def test_bf16_dft_matches_fft(self, rng, monkeypatch, s, q, dim):
        """The bf16 matmul-DFT route must agree with the complex-FFT
        route and with the f64 path to 2 % of the feature scale, for an
        even and an odd S, one to three levels, both orientations."""
        import libskylark_tpu.sketch.ppt as pptmod

        monkeypatch.setattr(pptmod, "_DFT_MIN_BATCH", 8)
        monkeypatch.setenv("SKYLARK_PPT_DFT", "1")  # CPU: force the chip's route
        monkeypatch.delenv("SKYLARK_NO_PPT_DFT", raising=False)
        n, m = 24, 64
        A = rng.standard_normal((n, m))
        A = A if dim == "columnwise" else A.T
        F = PPT(n, s, SketchContext(seed=7), q=q, c=0.7, gamma=1.3)
        A16 = jnp.asarray(A).astype(jnp.bfloat16)
        assert F._dft_wins(jnp.dtype(jnp.bfloat16), m)
        Z_dft = F.apply(A16, dim)
        assert Z_dft.dtype == jnp.bfloat16 and Z_dft.shape == ((s, m) if dim == "columnwise" else (m, s))
        monkeypatch.setenv("SKYLARK_NO_PPT_DFT", "1")
        Z_fft = np.asarray(F.apply(A16, dim), np.float64)
        Z64 = np.asarray(F.apply(jnp.asarray(A), dim))
        Z_dft = np.asarray(Z_dft, np.float64)
        scale = np.max(np.abs(Z64))
        assert np.max(np.abs(Z_dft - Z_fft)) / scale < 0.02
        assert np.max(np.abs(Z_dft - Z64)) / scale < 0.02

    @pytest.mark.parametrize("s", [16, 15])
    def test_half_spectrum_is_the_full_spectrum_at_the_kept_frequencies(self, rng, s):
        """A level's spectrum on the DFT route, W·Hc and W·Hs, is the
        full-spectrum (cos, sin) product with the same bf16 tables at
        frequencies 0…⌈S/2⌉−1, the real Nyquist term in the imaginary
        half's column 0 (zero for odd S); and the inverse tables give
        the full inverse's real part of that spectrum.  Products of bf16
        values summed in float64 are exact: the forward comparisons are
        too."""
        h = -(-s // 2)
        Hc, Hs, G = (np.asarray(T, np.float64)
                     for T in PPT(8, s, SketchContext(seed=7), q=1)._dft_tables())
        j = np.arange(s)
        theta = np.float32(2 * np.pi / s) * ((j[:, None] * j[None, :]) % s).astype(np.float32)
        C, Sn = (np.asarray(jnp.asarray(f(theta)).astype(jnp.bfloat16), np.float64)
                 for f in (np.cos, np.sin))
        W = np.asarray(jnp.asarray(rng.standard_normal((32, s))).astype(jnp.bfloat16), np.float64)
        Re, Im = W @ C, -(W @ Sn)
        np.testing.assert_array_equal(W @ Hc, Re[:, :h])
        np.testing.assert_array_equal((W @ Hs)[:, 1:], Im[:, 1:h])
        np.testing.assert_array_equal((W @ Hs)[:, 0], Re[:, s // 2] if s % 2 == 0 else 0)
        # the inverse: Re·C − Im·Sn over all S frequencies, here the kept
        # ones with the conjugate halves folded into G (the bf16
        # tables' conjugate columns agree but for entries of cos ±π/2,
        # which round to ±4e-8, not 0)
        P = np.concatenate([Re[:, :h], (W @ Hs)], axis=1)
        full = Re @ C - Im @ Sn
        np.testing.assert_allclose(P @ G.reshape(2 * h, s), full,
                                   rtol=0, atol=1e-6 * np.max(np.abs(full)))
        np.testing.assert_allclose(full / s, W, rtol=0, atol=1e-2 * np.max(np.abs(W)))

    # -- the CountSketch folded into the forward tables ------------------------

    @pytest.fixture
    def dft_route(self, monkeypatch):
        """The chip's bf16 DFT route, forced on the CPU for batches of 8."""
        import libskylark_tpu.sketch.ppt as pptmod

        monkeypatch.setattr(pptmod, "_DFT_MIN_BATCH", 8)
        monkeypatch.setenv("SKYLARK_PPT_DFT", "1")
        monkeypatch.delenv("SKYLARK_NO_PPT_DFT", raising=False)
        return monkeypatch

    @staticmethod
    def _composed(F, X):
        """The features of the rows of X (float64) on the DFT route's
        tables without a rounding between them: each level's CountSketch
        of √γ·x plus √c·s_l at bucket h_l (the map's draws read as
        data), its half spectrum through ``Hc`` and ``Hs``, the levels'
        product, and the inverse through ``G``."""
        Hc, Hs, G = (np.asarray(T, np.float64) for T in F._dft_tables())
        idx, val = (np.asarray(a) for a in F._hash_consts(jnp.float32))
        Pr = Pi = None
        for l, cwt in enumerate(F._cwts):
            b = np.asarray(cwt.buckets())
            v = np.asarray(cwt.values(jnp.float32), np.float64)
            W = np.zeros((X.shape[0], F.s))
            np.add.at(W.T, b, np.sqrt(F.gamma) * v[:, None] * X.T)
            W[:, idx[l]] += np.sqrt(F.c) * val[l]
            Re, Im = W @ Hc, W @ Hs
            if Pr is None:
                Pr, Pi = Re, Im
            else:  # column 0 holds two reals, frequency 0 and Nyquist
                real = np.arange(Re.shape[1]) == 0
                Pr, Pi = (Pr * Re - np.where(real, 0.0, Pi * Im),
                          np.where(real, Pi * Im, Pr * Im + Pi * Re))
        return np.concatenate([Pr, Pi], axis=1) @ G.reshape(-1, F.s) / F.s

    @pytest.mark.parametrize("q", [1, 3])
    @pytest.mark.parametrize("s", [16, 15])
    def test_folded_tables_are_the_signed_table_rows_at_the_buckets(self, dft_route, s, q):
        """v_l ⊙ Hc[b_l] and v_l ⊙ Hs[b_l] bit for bit, the constant's
        spectrum √c·s_l·Hc[h_l] and √c·s_l·Hs[h_l] in f32, and the
        inverse's G as the unfolded route has it."""
        F = PPT(24, s, SketchContext(seed=11), q=q, c=0.7, gamma=1.3)
        assert F._folds()
        consts = F._hash_consts(jnp.float32)
        Tc, Ts, Rc, Rs, G = F._tables(consts)
        Hc, Hs, G0 = (np.asarray(T) for T in F._dft_tables())
        np.testing.assert_array_equal(np.asarray(G).view(np.uint16), G0.view(np.uint16))
        for l, cwt in enumerate(F._cwts):
            b, v = np.asarray(cwt.buckets()), np.asarray(cwt.values(jnp.bfloat16))
            for T, H in ((Tc[l], Hc), (Ts[l], Hs)):
                assert T.dtype == jnp.bfloat16 and T.shape == (24, -(-s // 2))
                np.testing.assert_array_equal(np.asarray(T).view(np.uint16),
                                              (v[:, None] * H[b]).view(np.uint16))
        idx, val = (np.asarray(a) for a in consts)
        r = np.float32(np.sqrt(0.7)) * val[:, None]
        np.testing.assert_array_equal(np.asarray(Rc), r * Hc[idx].astype(np.float32))
        np.testing.assert_array_equal(np.asarray(Rs), r * Hs[idx].astype(np.float32))

    @pytest.mark.parametrize("dim", ["columnwise", "rowwise"])
    @pytest.mark.parametrize("q", [1, 2, 3])
    @pytest.mark.parametrize("s", [64, 15])
    def test_folded_features_are_the_hash_then_the_half_spectrum(self, rng, dft_route, s, q, dim):
        """The folded route's features are the CountSketch composed with
        the half-spectrum DFT on the same bf16 tables, with no rounding
        between them, to bf16 feature accuracy (the spectra's product
        and the features round to bf16: ≤ 0.5 % of the scale read)."""
        n, m = 24, 64
        F = PPT(n, s, SketchContext(seed=12), q=q, c=0.7, gamma=1.3)
        X = jnp.asarray(rng.standard_normal((m, n))).astype(jnp.bfloat16)
        Z = F.apply(X if dim == "rowwise" else X.T, dim)
        Z = np.asarray(Z if dim == "rowwise" else Z.T, np.float64)
        want = self._composed(F, np.asarray(X, np.float64))
        assert np.max(np.abs(Z - want)) / np.max(np.abs(want)) < 1e-2

    @pytest.mark.parametrize("dim", ["columnwise", "rowwise"])
    @pytest.mark.parametrize("fold", [True, False], ids=["fold", "unfold"])
    def test_the_fold_engages_by_the_one_hot_shape(self, rng, dft_route, fold, dim):
        """The tables fold the CountSketches in where their one-hot
        operands exist (n·S ≤ ``_ONEHOT_LIMIT``), else the hash and the
        transform stay apart; either way ``apply_with_operands`` is
        ``apply`` bit for bit, and the features are the complex FFT
        route's to 2 % of the feature scale."""
        from libskylark_tpu.sketch.hash import HashSketch

        assert PPT(1 << 15, 4096, SketchContext(seed=0), q=2)._folds()
        assert not PPT((1 << 15) + 1, 4096, SketchContext(seed=0), q=2)._folds()
        if not fold:  # this map's n·S over the limit
            dft_route.setattr(HashSketch, "_ONEHOT_LIMIT", 24 * 64 - 1)
        n, m, s = 24, 64, 64
        F = PPT(n, s, SketchContext(seed=13), q=2, c=0.7, gamma=1.3)
        assert F._folds() == fold
        ops = F.hoistable_operands(jnp.bfloat16)
        assert len(ops[2]) == (5 if fold else 3)
        # folded into the tables, or (over the limit) no one-hot operands
        assert ops[0] == (None, None)
        A = jnp.asarray(rng.standard_normal((m, n))).astype(jnp.bfloat16)
        A = A if dim == "rowwise" else A.T
        Z = np.asarray(F.apply(A, dim))
        np.testing.assert_array_equal(np.asarray(F.apply_with_operands(ops, A, dim)), Z)
        hoisted = jax.jit(lambda A: F.apply_with_operands(F.hoistable_operands(jnp.bfloat16), A, dim))
        np.testing.assert_array_equal(
            np.asarray(hoisted(A)), np.asarray(jax.jit(lambda A: F.apply(A, dim))(A)))
        dft_route.setenv("SKYLARK_NO_PPT_DFT", "1")
        Z_fft = np.asarray(F.apply(A, dim), np.float64)
        Z = Z.astype(np.float64)
        assert np.max(np.abs(Z - Z_fft)) / np.max(np.abs(Z_fft)) < 0.02
