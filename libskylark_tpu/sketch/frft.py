"""Fastfood feature maps (Le-Sarlós-Smola ICML'13).

≙ ``sketch/FRFT_data.hpp`` / ``sketch/FRFT_Elemental.hpp``: the dense
Gaussian W of the RFT is replaced per block by ``Sm·H·G·Π·H·B`` — B a
Rademacher diagonal, Π a random permutation, G a Gaussian diagonal, H the
fast unitary transform, Sm a kernel-dependent scaling
(``FRFT_data.hpp:100-140``); features are then
``√(2/S)·cos(V·x + shift)``.

Counter budget mirrors ``FastRFT_data_t::build`` (shifts S; B, G, Π each
numblks·NB).  The reference's Fisher-Yates permutation
(``FRFT_data.hpp:115-125``) becomes an argsort of counter-derived uniform
keys — same distribution, shard-local computable, O(NB log NB) on device.

With the orthonormal WHT, Var((H·G·Π·H·B x)_i) = ‖x‖²/NB, so the Gaussian
scaling is ``Sm = √NB/σ`` (the reference's ``1/(σ√N)`` compensates its
*unnormalized* FUT); FastMatern multiplies per-row ``sqrt(2ν/χ²_{2ν})``
like MaternRFT (``FRFT_data.hpp:208+``).

TPU fast path (round 3): for batched bf16/f32 inputs the per-block chain
``Sm·H·G·Π·H·B`` is **realized as a dense (S, n) matrix in-graph** (two
nb×nb WHTs — cheap next to the batch) and applied as one MXU matmul.
The streaming form's permutation is a lane gather over the whole batch —
far below HBM streaming rate on TPU — while the realized form folds Π
into the matrix for free; measured 34.0→16.1 ms bf16 and 65.1→51.2 ms
f32 at 131072×4096→2048 on v5e (at S=4096 f32 the four split passes
lose to the S-independent streaming sweep — see ``_REALIZE_MAX_RATIO``).
f32 rides a 4-pass bf16 split (A's three split
parts against W_hi, plus A_hi against W_lo): unlike FJLT's ±1 operand,
W is Gaussian-valued, so bf16 needs the W_lo correction too; the dropped
``W_lo·(A_lo+A_lo2)`` terms leave ~2^-16-relative pre-cos error — below
the feature map's own O(1/√S) Monte-Carlo error by orders of magnitude
(guarded on hardware in tests/_hw_guards.py).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from ..core.context import SketchContext
from ..core.precision import bf16_split3
from ..core.random import chi2_lanes, sample
from .base import Dimension, SketchTransform, register_sketch
from .fut import next_pow2, wht

__all__ = ["FastRFT", "FastGaussianRFT", "FastMaternRFT"]

_TWO_PI = 2.0 * np.pi

# Realized-W gate: the in-graph W build costs two nb×nb-column WHTs (the
# streaming form pays the same per nb batch columns), so the matmul form
# pays off once the batch is several nb wide; the cap bounds W's (padded
# S × nb) f32 footprint (64M entries = 256 MB) so huge s×nb combinations
# keep the O(nb·m)-resident streaming form.
_REALIZE_MIN_BATCH_BLOCKS = 4
_REALIZE_MAX_ELEMENTS = 64 << 20
# Measured v5e crossover (131072×4096, r3 probe): the realized matmul
# costs ~(passes)·2·n·S·m MXU flops while the streaming form costs two
# WHT HBM/compute sweeps + a permutation gather per nb-block, ∝
# numblks·nb·m.  Realized wins while S·n ≤ K·numblks·nb; fitting the
# measurements (bf16 16.1 ms at S=2048 vs 34.0 streaming, 30.3 vs 38.0
# at S=4096; f32 51.2 vs 65.1 at S=2048 but 102 vs 66.8 at S=4096 — the
# four split passes lose to the S-independent streaming sweep) gives
# K≈4340 bf16 / ≈2670 f32; rounded down conservatively.
_REALIZE_MAX_RATIO = {jnp.bfloat16: 4096.0, jnp.float32: 2560.0}


class FastRFT(SketchTransform):
    """Base Fastfood engine; subclasses set the Sm scaling."""

    def __init__(self, n: int, s: int, context: SketchContext):
        super().__init__(n, s, context)
        self._seed = context.seed
        self._nb = next_pow2(n)
        self.numblks = 1 + (s - 1) // self._nb
        self.outscale = np.sqrt(2.0 / s)
        # ≙ FastRFT_data_t::build reserve order: shifts, B, G, P.
        self._shift_base = context.reserve(s)
        self._b_base = context.reserve(self.numblks * self._nb)
        self._g_base = context.reserve(self.numblks * self._nb)
        self._p_base = context.reserve(self.numblks * self._nb)

    # -- counter-derived pieces --------------------------------------------

    def _shifts(self, dtype):
        return sample(
            "uniform", self._seed, self._shift_base, self.s,
            dtype=dtype, low=0.0, high=_TWO_PI,
        )

    def _B(self, dtype):
        return sample(
            "rademacher", self._seed, self._b_base, self.numblks * self._nb, dtype=dtype
        ).reshape(self.numblks, self._nb)

    def _G(self, dtype):
        return sample(
            "normal", self._seed, self._g_base, self.numblks * self._nb, dtype=dtype
        ).reshape(self.numblks, self._nb)

    def _perms(self):
        keys = sample(
            "uniform", self._seed, self._p_base, self.numblks * self._nb,
            dtype=jnp.float32,
        ).reshape(self.numblks, self._nb)
        return jnp.argsort(keys, axis=1)

    def _sm(self, dtype):
        """Kernel scaling, shape (numblks·NB,) (≙ Sm; 1.0 in the base)."""
        return jnp.ones((self.numblks * self._nb,), dtype)

    def _features(self, X):
        """V·X for columnwise X (n, m) → (S, m) pre-cos features."""
        nb = self._nb
        Xp = jnp.pad(X, ((0, nb - self.n), (0, 0))) if nb != self.n else X
        B = self._B(X.dtype)
        G = self._G(X.dtype)
        perms = self._perms()
        # All blocks at once: (blk, nb, m) — vmapped butterfly-free WHT.
        T = wht(B[:, :, None] * Xp[None, :, :], axis=1)
        T = jnp.take_along_axis(T, perms[:, :, None], axis=1)
        T = G[:, :, None] * T
        T = wht(T, axis=1)
        V = T.reshape(self.numblks * nb, -1) * self._sm(X.dtype)[:, None]
        return V[: self.s]

    # -- realized-W fast path ----------------------------------------------

    def _realize_wins(self, dtype, batch: int) -> bool:
        """Gate for realizing Sm·H·G·Π·H·B as a dense (S, n) matrix and
        applying it as one MXU matmul (see module docstring).  TPU-only
        by default (the crossover constants are v5e-measured, and on CPU
        the f32 4-pass split is both slower and less accurate than the
        exact streaming form); ``SKYLARK_FRFT_GEMM=1`` forces it on for
        cross-backend tests, ``SKYLARK_NO_FRFT_GEMM=1`` forces it off."""
        if os.environ.get("SKYLARK_NO_FRFT_GEMM", "0") == "1":
            return False
        if (
            jax.default_backend() != "tpu"
            and os.environ.get("SKYLARK_FRFT_GEMM", "0") != "1"
        ):
            return False
        key = jnp.dtype(dtype).type
        if key not in _REALIZE_MAX_RATIO:
            return False  # f64 (CPU parity) keeps the exact streaming form
        if self.numblks * self._nb * self._nb > _REALIZE_MAX_ELEMENTS:
            return False
        if self.s * self.n > _REALIZE_MAX_RATIO[key] * self.numblks * self._nb:
            return False
        return batch >= _REALIZE_MIN_BATCH_BLOCKS * self._nb

    def _realized_w(self):
        """(S, n) f32 matrix of the full per-block chain, built in-graph
        from the counter stream (same windows as the streaming form, so
        values match it exactly up to matmul rounding).  Columns beyond n
        would multiply padding zeros and are sliced away."""
        return self._features(jnp.eye(self.n, dtype=jnp.float32)).astype(
            jnp.float32  # belt-and-braces: subclass _sm dtype leaks
        )

    def hoistable_operands(self, dtype):
        """(realized W, shifts) for streaming consumers.  No backend or
        batch gate: a hoisting consumer amortizes the in-graph W build
        over its whole panel loop, which dominates both crossovers (the
        per-call ``_realize_wins`` gates exist because plain ``apply``
        rebuilds W every call)."""
        key = jnp.dtype(dtype).type
        if key not in (jnp.bfloat16, jnp.float32):
            return None  # f64 keeps the exact streaming form
        if self.numblks * self._nb * self._nb > _REALIZE_MAX_ELEMENTS:
            return None
        return (self._realized_w(), self._shifts(jnp.float32))

    def apply_with_operands(
        self, ops, A, dim: Dimension | str = Dimension.COLUMNWISE
    ):
        dim = Dimension.of(dim)
        A = jnp.asarray(A) if not hasattr(A, "todense") else A
        if (
            ops is None
            or hasattr(A, "todense")
            or A.ndim != 2
            or A.dtype not in (jnp.bfloat16, jnp.float32)
        ):
            return self.apply(A, dim)
        rowwise = dim is Dimension.ROWWISE
        if A.shape[1 if rowwise else 0] != self.n:
            raise ValueError(
                f"{dim.value} apply needs {self.n} on the sketched axis, "
                f"got {A.shape}"
            )
        return self._apply_realized(A, rowwise=rowwise, dtype=A.dtype, ops=ops)

    def _apply_realized(self, A, rowwise: bool, dtype, ops=None):
        """V = W·X (or X·Wᵀ rowwise) on the MXU; bf16 inputs take one
        bf16 matmul, f32 a 4-pass bf16 split (A_hi/lo/lo2 × W_hi plus
        A_hi × W_lo — the W_lo·A_lo tail is ~2^-16-relative, dropped)."""
        W, sh = ops if ops is not None else (self._realized_w(), None)
        # rowwise: X (m, n)·Wᵀ → contract X₁ with W₁; columnwise:
        # W (S, n)·X (n, m) → contract W₁ with X₀.
        contract = (((1,), (1,)), ((), ())) if rowwise else (((1,), (0,)), ((), ()))

        def mm(x, w):
            args = (x, w) if rowwise else (w, x)
            return jax.lax.dot_general(
                *args, contract, preferred_element_type=jnp.float32
            )

        if dtype == jnp.bfloat16:
            V = mm(A, W.astype(jnp.bfloat16))
        else:
            w_hi, w_lo, _ = bf16_split3(W)
            a_hi, a_lo, a_lo2 = bf16_split3(A)
            V = mm(a_hi, w_hi) + mm(a_lo, w_hi) + mm(a_lo2, w_hi) + mm(a_hi, w_lo)
        if sh is None:
            sh = self._shifts(jnp.float32)
        Z = self.outscale * jnp.cos(V + (sh[None, :] if rowwise else sh[:, None]))
        return Z.astype(dtype)

    def apply(self, A, dim: Dimension | str = Dimension.COLUMNWISE):
        dim = Dimension.of(dim)
        A = jnp.asarray(A)
        dtype = A.dtype if jnp.issubdtype(A.dtype, jnp.floating) else jnp.float32
        A = A.astype(dtype)
        squeeze = A.ndim == 1
        if dim is Dimension.COLUMNWISE:
            X = A[:, None] if squeeze else A
            if X.shape[0] != self.n:
                raise ValueError(f"columnwise apply needs {self.n} rows, got {A.shape}")
            if X.ndim == 2 and self._realize_wins(dtype, X.shape[1]):
                Z = self._apply_realized(X, rowwise=False, dtype=dtype)
                return Z[:, 0] if squeeze else Z
            V = self._features(X)
            Z = self.outscale * jnp.cos(V + self._shifts(dtype)[:, None])
            return Z[:, 0] if squeeze else Z
        X = A[None, :] if squeeze else A
        if X.shape[-1] != self.n:
            raise ValueError(f"rowwise apply needs {self.n} cols, got {A.shape}")
        if X.ndim == 2 and self._realize_wins(dtype, X.shape[0]):
            Z = self._apply_realized(X, rowwise=True, dtype=dtype)
            return Z[0] if squeeze else Z
        V = self._features(X.T).T
        Z = self.outscale * jnp.cos(V + self._shifts(dtype)[None, :])
        return Z[0] if squeeze else Z


@register_sketch
class FastGaussianRFT(FastRFT):
    """≙ ``FastGaussianRFT_data_t`` (FRFT_data.hpp:147-205)."""

    sketch_type = "FastGaussianRFT"

    def __init__(self, n, s, context, sigma: float = 1.0):
        self.sigma = float(sigma)
        super().__init__(n, s, context)

    def _sm(self, dtype):
        return jnp.full(
            (self.numblks * self._nb,), np.sqrt(self._nb) / self.sigma, dtype
        )

    def _param_dict(self):
        return {"sigma": self.sigma}

    @classmethod
    def _from_param_dict(cls, d, context):
        return cls(d["N"], d["S"], context, sigma=d["sigma"])


@register_sketch
class FastMaternRFT(FastRFT):
    """≙ ``FastMaternRFT_data_t``: per-row multivariate-t correction."""

    sketch_type = "FastMaternRFT"

    def __init__(self, n, s, context, nu: float = 1.0, l: float = 1.0):
        two_nu = 2.0 * nu
        if abs(two_nu - round(two_nu)) > 1e-9 or round(two_nu) < 1:
            raise ValueError(f"FastMaternRFT needs 2*nu a positive integer, got nu={nu}")
        self.nu = float(nu)
        self.l = float(l)
        super().__init__(n, s, context)
        self._chi_base = context.reserve(self.numblks * self._nb)

    def _sm(self, dtype):
        two_nu = int(round(2 * self.nu))
        size = self.numblks * self._nb
        chi2 = chi2_lanes(self._seed, self._chi_base, size, two_nu, dtype)
        # Scalar as a typed jnp value: a bare np.float64 would promote the
        # whole Sm (and then W / the streaming features) to f64 under x64.
        scale = jnp.asarray(np.sqrt(self._nb) / self.l, dtype)
        return jnp.sqrt(2.0 * self.nu / chi2) * scale

    def _param_dict(self):
        return {"nu": self.nu, "l": self.l}

    @classmethod
    def _from_param_dict(cls, d, context):
        return cls(d["N"], d["S"], context, nu=d["nu"], l=d["l"])
