"""Random Fourier feature maps (Rahimi-Recht) and QMC variants.

≙ ``sketch/RFT_data.hpp`` / ``sketch/RFT_Elemental.hpp`` (the apply is
``Z = outscale · cos(scale_i · (W·X)_i + shift_i)`` with W the underlying
counter-based dense transform pre-scaled by ``inscale``,
``RFT_Elemental.hpp:85-120``) and ``sketch/QRFT_data.hpp`` (W from a
leaped Halton sequence through the inverse CDF; shifts from the sequence's
extra dimension N, ``QRFT_data.hpp:29-107``).

Concrete kernels (constructor params ≙ the reference's data classes):

- GaussianRFT(sigma):   W ~ N, inscale 1/σ, outscale √(2/S)
- LaplacianRFT(sigma):  W ~ Cauchy, inscale 1/σ, outscale √(2/S)
- MaternRFT(nu, l):     W ~ N with per-row multivariate-t correction
  ``sqrt(2ν/χ²_{2ν})`` (``RFT_data.hpp:336-345``), inscale 1/l
- GaussianQRFT / LaplacianQRFT(sigma, skip): QMC rows

The W·X product is the MXU-heavy op; the epilogue fuses into it under
XLA.  The cosine is NOT free there: XLA's general f32 cosine (argument
reduction good for any magnitude, in software on the VPU) cost a bf16
feature pass 3.5 times its GEMM on a v5e (PERF.md section 6, PR 32).
So the epilogue is chosen by the operand's dtype (``_epilogue_kernel``):

- operands narrower than f32 (bfloat16, float16): the phase is taken
  from the product's f32 accumulator, never rounded to the operand's
  dtype, counted in turns ``u = acc·scale/2π + shift/2π``, reduced in one
  step ``r = u − ⌊u + ½⌋`` and the cosine evaluated by a fixed even
  polynomial in f32 — six multiply-adds, good to 5e-7, where the
  features are then rounded to 8 or 11 bits;
- f32 / f64 operands: ``outscale·cos(scale·WX + shift)`` with XLA's
  cosine, as ever (a one-step reduction in f32 loses the phase's low
  bits at large magnitude, and the f32 map is nobody's bottleneck).

(The reference hand-loops this with OpenMP and an inexact-cosine
fallback, ``RFT_Elemental.hpp:85-120``.)
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..core.context import SketchContext
from ..core.quasirand import LeapedHaltonSequence
from ..core.random import chi2_lanes, sample
from .base import Dimension, SketchTransform, register_sketch
from .dense import DenseSketch, _matmul, _matmul_nt

__all__ = [
    "RFT",
    "GaussianRFT",
    "LaplacianRFT",
    "MaternRFT",
    "GaussianQRFT",
    "LaplacianQRFT",
]

_TWO_PI = 2.0 * np.pi
_INV_TWO_PI = 1.0 / _TWO_PI

# cos(2π r) = P(r²) on r ∈ [−½, ½]: the degree-6 minimax polynomial in
# t = r² on [0, ¼] (Remez, float64: |error| ≤ 1.1e-8), constant term
# first.  Evaluated by Horner's rule in f32 it is within 4.3e-7 of the
# cosine over the whole interval.
_COS_TURN_POLY = (
    0.9999999891722795,
    -19.73920453209491,
    64.93911898390914,
    -85.4501655505192,
    60.16783173261327,
    -25.96831330580957,
    6.529610419788367,
)


def _is_narrow(dtype) -> bool:
    """Narrower than f32 (bfloat16, float16, the fp8 family)."""
    dtype = jnp.dtype(dtype)
    return jnp.issubdtype(dtype, jnp.floating) and jnp.finfo(dtype).bits < 32


def _accumulator_dtype(dtype):
    """What the W·X product of operands in ``dtype`` hands the epilogue:
    the f32 accumulator for narrow operands, None (their own) else."""
    return jnp.float32 if _is_narrow(dtype) else None


def _feature_dtype(A, WX):
    """Features come back in the operand's dtype: ``WX``'s, unless the
    product of a narrow operand was handed over as its accumulator."""
    dtype = getattr(A, "dtype", None)
    return dtype if dtype is not None and _is_narrow(dtype) else WX.dtype


def _cos_turns(u, amplitude=1.0):
    """``amplitude·cos(2π u)`` in f32 for a phase ``u`` counted in turns:
    one-step reduction to r = u − ⌊u + ½⌋ ∈ [−½, ½], then
    ``_COS_TURN_POLY`` in r² by Horner's rule, the (static) amplitude
    folded into its coefficients.  The reduction is exact; ``u`` itself
    carries half an ulp of its magnitude (1e-6 of a turn at 64 radians).
    ``floor`` and not ``round``: on a v5e ``round-nearest-even`` costs six
    VPU operations more a register, and the chain is bound by them
    (PERF.md section 6, PR 32); the two differ only at r = ±½, where the
    even polynomial reads the same."""
    r = u - jnp.floor(u + jnp.asarray(0.5, u.dtype))
    t = r * r
    p = jnp.asarray(amplitude * _COS_TURN_POLY[-1], u.dtype)
    for c in _COS_TURN_POLY[-2::-1]:
        p = p * t + jnp.asarray(amplitude * c, u.dtype)
    return p


@partial(jax.jit, static_argnames=("outscale", "columnwise", "out_dtype"))
def _epilogue_kernel(WX, shifts, scales, *, outscale, columnwise, out_dtype):
    """The feature-map epilogue as one compiled kernel (``scales`` may be
    None — it drops out of the pytree).  Both the eager apply and the
    plan layer's fused executables inline this same chain, keeping them
    bit-identical.

    ``out_dtype`` — the operand's dtype — picks the chain.  f32/f64:
    ``outscale·cos(scales·WX + shifts)`` in that dtype, XLA's cosine.
    Narrower: ``WX`` is the product's f32 accumulator (a BCOO product
    arrives rounded; it is widened and treated alike), ``shifts`` and
    ``scales`` arrive in turns (both already over 2π, f32), and the
    cosine is :func:`_cos_turns`."""
    col = columnwise and WX.ndim > 1

    def per_feature(v):
        return v[:, None] if col else v

    if not _is_narrow(out_dtype):
        if scales is not None:
            WX = WX * per_feature(scales)
        WX = WX + per_feature(shifts)
        return jnp.asarray(outscale, WX.dtype) * jnp.cos(WX)
    with jax.named_scope("rft.epilogue.turns"):
        f32 = jnp.float32
        per_turn = (
            jnp.asarray(_INV_TWO_PI, f32) if scales is None
            else per_feature(scales)
        )
        u = WX.astype(f32) * per_turn + per_feature(shifts)
        return _cos_turns(u, outscale).astype(out_dtype)


class RFT(SketchTransform):
    """Base engine: Z = outscale · cos(scales ⊙ (W·X) + shifts)."""

    w_dist = "normal"

    def __init__(
        self,
        n: int,
        s: int,
        context: SketchContext,
        inscale: float,
        outscale: float,
    ):
        super().__init__(n, s, context)
        self._seed = context.seed
        self.inscale = float(inscale)
        self.outscale = float(outscale)
        # Counter budget ≙ RFT_data_t::build: N*S for W, then S shifts.
        self._underlying = _Underlying(n, s, context, inscale, self.w_dist)
        self._shift_base = context.reserve(s)

    def shifts(self, dtype=jnp.float32):
        """The S phase shifts, memoized per dtype as a CONCRETE array
        (computed eagerly even when called mid-trace, where it enters
        the trace as a tiny (S,) constant).  Concreteness matters beyond
        speed: regenerated inside a jit fusion, the uniform conversion's
        ``bits·scale + low`` contracts with the epilogue's add into an
        FMA, and the planned apply would drift a ulp from eager."""
        dtype = jnp.dtype(dtype)
        cache = self.__dict__.setdefault("_shift_cache", {})
        hit = cache.get(dtype.name)
        if hit is None:
            with jax.ensure_compile_time_eval():
                hit = cache[dtype.name] = sample(
                    "uniform",
                    self._seed,
                    self._shift_base,
                    self.s,
                    dtype=dtype,
                    low=0.0,
                    high=_TWO_PI,
                )
        return hit

    def scales(self, dtype=jnp.float32):
        """Per-feature scaling; identity unless a subclass overrides
        (≙ ``_scales`` filled with 1, ``RFT_data.hpp:88-90``)."""
        return None

    def _turns(self):
        """``(shifts, scales)`` over 2π in f32, as the narrow-operand
        chain of :func:`_epilogue_kernel` takes them: realized once a
        map and concrete, for the reason :meth:`shifts` gives."""
        hit = self.__dict__.get("_turn_cache")
        if hit is None:
            with jax.ensure_compile_time_eval():
                inv = jnp.asarray(_INV_TWO_PI, jnp.float32)
                scales = self.scales(jnp.float32)
                hit = self.__dict__["_turn_cache"] = (
                    self.shifts(jnp.float32) * inv,
                    None if scales is None else scales * inv,
                )
        return hit

    def apply(self, A, dim: Dimension | str = Dimension.COLUMNWISE):
        dim = Dimension.of(dim)
        WX = self._underlying.apply(A, dim)
        return self._epilogue(WX, dim, _feature_dtype(A, WX))

    def _epilogue(self, WX, dim: Dimension, out_dtype):
        """outscale · cos(scales ⊙ WX + shifts) in ``out_dtype`` — via
        the shared jitted kernel so the eager and planned paths run the
        SAME fused elementwise chain (op-by-op eager dispatch skips the
        FMA contraction a jit fusion applies to ``WX·scales + shifts``,
        and the two would differ by a ulp)."""
        if _is_narrow(out_dtype):
            shifts, scales = self._turns()
        else:
            shifts, scales = self.shifts(WX.dtype), self.scales(WX.dtype)
        return _epilogue_kernel(
            WX,
            shifts,
            scales,
            outscale=self.outscale,
            columnwise=dim is Dimension.COLUMNWISE,
            out_dtype=jnp.dtype(out_dtype),
        )

    def _apply_slice_columnwise(self, A_block, start: int):
        """Partial W·A over the coordinate block: the LINEAR half of the
        feature map decomposes over row blocks exactly like the dense
        engine; the nonlinear cos epilogue must wait for the full sum and
        runs in :meth:`finalize_slices`."""
        return self._underlying._apply_slice_columnwise(A_block, start)

    supports_slice_kernel = True

    def apply_slice_kernel(self, A_block, start):
        """jit-safe linear half with traced ``start`` — same delegation
        as :meth:`_apply_slice_columnwise` (the cos epilogue still runs
        in :meth:`finalize_slices` once the slice-sums are merged)."""
        return self._underlying.apply_slice_kernel(A_block, start)

    def finalize_slices(
        self, acc, dim: Dimension | str = Dimension.COLUMNWISE, dtype=None
    ):
        """COLUMNWISE slice-sums hold the merged W·A — apply the
        ``outscale·cos(scales ⊙ · + shifts)`` epilogue once here.
        ROWWISE blocks were finished by :meth:`apply` already.

        ``dtype`` is the blocks' dtype where it is not ``acc``'s: the
        slices of bfloat16 blocks come back as f32 accumulators, and
        ``finalize_slices(acc, dim, jnp.bfloat16)`` is then bit for bit
        the bfloat16 :meth:`apply`."""
        dim = Dimension.of(dim)
        if dim is Dimension.ROWWISE:
            return acc
        return self._epilogue(acc, dim, acc.dtype if dtype is None else dtype)

    def hoistable_operands(self, dtype):
        """The realized (S, N) W — loop-invariant, and the expensive
        part of the apply to re-derive (Box-Muller per visit).
        Delegates to the underlying dense engine (one gate, one realize
        — and JLT/CT streaming consumers get the same seam)."""
        return self._underlying.hoistable_operands(dtype)

    def apply_with_operands(
        self, ops, A, dim: Dimension | str = Dimension.COLUMNWISE
    ):
        dim = Dimension.of(dim)
        WX = self._underlying.apply_with_operands(ops, A, dim)
        return self._epilogue(WX, dim, _feature_dtype(A, WX))


class _Underlying(DenseSketch):
    """The dense W (pre-scaled by inscale); not registered — internal.
    Its products of narrow operands come back as the f32 accumulator,
    which the epilogue reads unrounded."""

    def __init__(self, n, s, context, scale, dist):
        self.dist = dist
        super().__init__(n, s, context, scale=scale)

    def _product_dtype(self, dtype):
        return _accumulator_dtype(dtype)


@register_sketch
class GaussianRFT(RFT):
    """Feature map for the Gaussian kernel exp(−‖x−y‖²/(2σ²))
    (≙ ``GaussianRFT_data_t``, RFT_data.hpp:103-172)."""

    sketch_type = "GaussianRFT"
    w_dist = "normal"

    def __init__(self, n: int, s: int, context: SketchContext, sigma: float = 1.0):
        self.sigma = float(sigma)
        super().__init__(n, s, context, 1.0 / sigma, np.sqrt(2.0 / s))

    def _param_dict(self):
        return {"sigma": self.sigma}

    @classmethod
    def _from_param_dict(cls, d, context):
        return cls(d["N"], d["S"], context, sigma=d["sigma"])


@register_sketch
class LaplacianRFT(RFT):
    """Feature map for the Laplacian kernel exp(−‖x−y‖₁/σ)
    (≙ ``LaplacianRFT_data_t``, RFT_data.hpp:175-255: Cauchy W)."""

    sketch_type = "LaplacianRFT"
    w_dist = "cauchy"

    def __init__(self, n: int, s: int, context: SketchContext, sigma: float = 1.0):
        self.sigma = float(sigma)
        super().__init__(n, s, context, 1.0 / sigma, np.sqrt(2.0 / s))

    def _param_dict(self):
        return {"sigma": self.sigma}

    @classmethod
    def _from_param_dict(cls, d, context):
        return cls(d["N"], d["S"], context, sigma=d["sigma"])


@register_sketch
class MaternRFT(RFT):
    """Feature map for the Matérn(ν, ℓ) kernel: rows are multivariate-t —
    Gaussian row × ``sqrt(2ν/χ²_{2ν})`` (≙ ``MaternRFT_data_t::build``,
    RFT_data.hpp:336-345).

    The χ²_{2ν} draw needs integer 2ν (sum of squares of 2ν normals from
    independent counter lanes); all common Matérn orders (ν = ½, 1, 3/2,
    5/2, ...) qualify.
    """

    sketch_type = "MaternRFT"
    w_dist = "normal"

    def __init__(
        self, n: int, s: int, context: SketchContext, nu: float = 1.0, l: float = 1.0
    ):
        two_nu = 2.0 * nu
        if abs(two_nu - round(two_nu)) > 1e-9 or round(two_nu) < 1:
            raise ValueError(f"MaternRFT needs 2*nu a positive integer, got nu={nu}")
        self.nu = float(nu)
        self.l = float(l)
        super().__init__(n, s, context, 1.0 / l, np.sqrt(2.0 / s))
        self._scales_base = context.reserve(s)

    def scales(self, dtype=jnp.float32):
        dtype = jnp.dtype(dtype)
        cache = self.__dict__.setdefault("_scale_cache", {})
        hit = cache.get(dtype.name)
        if hit is None:
            with jax.ensure_compile_time_eval():
                two_nu = int(round(2 * self.nu))
                # χ²_{2ν} per feature row: sum over 2ν independent lanes.
                chi2 = chi2_lanes(
                    self._seed, self._scales_base, self.s, two_nu, dtype
                )
                hit = cache[dtype.name] = jnp.sqrt(2.0 * self.nu / chi2)
        return hit

    def _param_dict(self):
        return {"nu": self.nu, "l": self.l}

    @classmethod
    def _from_param_dict(cls, d, context):
        return cls(d["N"], d["S"], context, nu=d["nu"], l=d["l"])


class QRFT(SketchTransform):
    """Quasi-Monte-Carlo random features (Yang et al, ICML'14).

    W[j, d] = invCDF(seq(skip+j, d)) · inscale; shift_j = 2π·seq(skip+j, N)
    (≙ ``QRFT_data_t::build``, QRFT_data.hpp:84-95; sequence dim = N+1).
    Consumes no counters — reproducibility is carried by (sequence, skip).
    """

    w_dist = "normal"  # inverse-CDF target

    def __init__(
        self,
        n: int,
        s: int,
        context: SketchContext,
        inscale: float,
        outscale: float,
        skip: int = 0,
    ):
        super().__init__(n, s, context)
        self.inscale = float(inscale)
        self.outscale = float(outscale)
        self.skip = int(skip)
        self._sequence = LeapedHaltonSequence(n + 1)

    def _inv_cdf(self, u):
        if self.w_dist == "normal":
            return jax.scipy.special.ndtri(u)
        if self.w_dist == "cauchy":
            return jnp.tan(jnp.pi * (u - 0.5))
        raise ValueError(f"no inverse CDF for {self.w_dist}")

    def realize(self, dtype=jnp.float32):
        """(W, shifts): W is (S, N)."""
        U = self._sequence.window(self.skip, self.s, dtype=dtype)  # (S, N+1)
        W = self._inv_cdf(U[:, : self.n]) * jnp.asarray(self.inscale, dtype)
        shifts = _TWO_PI * U[:, self.n]
        return W.astype(dtype), shifts.astype(dtype)

    def apply(self, A, dim: Dimension | str = Dimension.COLUMNWISE):
        dim = Dimension.of(dim)
        A = jnp.asarray(A)
        dtype = A.dtype if jnp.issubdtype(A.dtype, jnp.floating) else jnp.float32
        W, shifts = self.realize(dtype)
        acc_dtype = _accumulator_dtype(dtype)
        if dim is Dimension.COLUMNWISE:
            if A.shape[0] != self.n:
                raise ValueError(f"columnwise apply needs {self.n} rows, got {A.shape}")
            WX = _matmul(W, A.astype(dtype), acc_dtype)
        else:
            if A.shape[-1] != self.n:
                raise ValueError(f"rowwise apply needs {self.n} cols, got {A.shape}")
            WX = _matmul_nt(A.astype(dtype), W, acc_dtype)
        if acc_dtype is not None:
            shifts = shifts.astype(acc_dtype) * jnp.asarray(_INV_TWO_PI, acc_dtype)
        return _epilogue_kernel(
            WX,
            shifts,
            None,
            outscale=self.outscale,
            columnwise=dim is Dimension.COLUMNWISE,
            out_dtype=jnp.dtype(dtype),
        )

    def _param_dict(self):
        return {"skip": self.skip}


@register_sketch
class GaussianQRFT(QRFT):
    """≙ ``GaussianQRFT_data_t`` (QRFT_data.hpp:118-140)."""

    sketch_type = "GaussianQRFT"
    w_dist = "normal"

    def __init__(self, n, s, context, sigma: float = 1.0, skip: int = 0):
        self.sigma = float(sigma)
        super().__init__(n, s, context, 1.0 / sigma, np.sqrt(2.0 / s), skip)

    def _param_dict(self):
        return {"sigma": self.sigma, "skip": self.skip}

    @classmethod
    def _from_param_dict(cls, d, context):
        return cls(d["N"], d["S"], context, sigma=d["sigma"], skip=d.get("skip", 0))


@register_sketch
class LaplacianQRFT(QRFT):
    """≙ ``LaplacianQRFT_data_t``: Cauchy inverse CDF."""

    sketch_type = "LaplacianQRFT"
    w_dist = "cauchy"

    def __init__(self, n, s, context, sigma: float = 1.0, skip: int = 0):
        self.sigma = float(sigma)
        super().__init__(n, s, context, 1.0 / sigma, np.sqrt(2.0 / s), skip)

    def _param_dict(self):
        return {"sigma": self.sigma, "skip": self.skip}

    @classmethod
    def _from_param_dict(cls, d, context):
        return cls(d["N"], d["S"], context, sigma=d["sigma"], skip=d.get("skip", 0))
