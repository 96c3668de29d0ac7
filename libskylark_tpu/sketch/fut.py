"""Fast unitary transforms: Walsh-Hadamard + DCT, and the RFUT sketch.

≙ the reference's FUT layer (``sketch/FUT.hpp:26-110``, FFTW DCT wrappers
``utility/fft/fftw_futs.h:10-140``, SpiralWHT) and ``RFUT_t``
(``sketch/RFUT.hpp:17``, ``sketch/RFUT_Elemental.hpp``).

TPU design: the Hadamard transform is computed by **Kronecker
factorization** — ``H_{2^k} = H_a ⊗ H_b ⊗ ...`` with each factor a dense
±1 matrix of size ≤ 256 — so the whole transform is a few MXU matmuls
(tensordots) instead of a log₂(n)-pass butterfly that would make log₂(n)
trips through HBM.  This is the TPU answer to SpiralWHT's cache-blocked
recursion.  DCT rides XLA's native FFT (``jax.scipy.fft.dct``), matching
the reference's FFTW ``REDFT10`` path.

All transforms here are orthonormal (Hᵀ·H = I), unlike FFTW's unnormalized
r2r kernels — scale factors in FJLT/Fastfood account for this explicitly.
"""

from __future__ import annotations

from functools import lru_cache

import jax.numpy as jnp
import jax.scipy.fft as jfft
import numpy as np

from ..core.context import SketchContext
from ..core.random import sample
from .base import Dimension, SketchTransform

__all__ = ["wht", "dct", "next_pow2", "RFUT"]

_MAX_FACTOR_LOG2 = 8  # dense Hadamard factors up to 256x256


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@lru_cache(maxsize=16)
def _hadamard(k: int) -> np.ndarray:
    """Dense 2^k × 2^k Sylvester Hadamard matrix (unnormalized, ±1)."""
    H = np.array([[1.0]])
    for _ in range(k):
        H = np.block([[H, H], [H, -H]])
    return H


def wht(x, axis: int = 0):
    """Orthonormal Walsh-Hadamard transform along ``axis`` (size 2^k).

    Sylvester (natural) ordering: row-major index factorization matches
    ``H = H_{f0} ⊗ H_{f1} ⊗ ...``, so the transform is a chain of small
    dense einsum contractions that XLA maps onto the MXU.  The factor
    axes are expanded *in place* (no moveaxis of the whole array): for
    multi-GB operands a front-transpose would cost two extra full HBM
    passes per factor.
    """
    x = jnp.asarray(x)
    axis = axis % x.ndim
    n = x.shape[axis]
    k = n.bit_length() - 1
    if n != (1 << k):
        raise ValueError(f"wht needs a power-of-2 size, got {n}")
    if n == 1:
        return x
    chunks = []
    rem = k
    while rem > 0:
        c = min(rem, _MAX_FACTOR_LOG2)
        chunks.append(c)
        rem -= c
    factors = [1 << c for c in chunks]
    lead = x.shape[:axis]
    trail = x.shape[axis + 1 :]
    x = x.reshape(*lead, *factors, *trail)
    # Einsum letters: leading dims, factor dims, trailing dims.
    nlead, nfac, ntrail = len(lead), len(factors), len(trail)
    letters = "abcdefghijklmnopqrstuvw"
    lead_l = letters[:nlead]
    fac_l = letters[nlead : nlead + nfac]
    trail_l = letters[nlead + nfac : nlead + nfac + ntrail]
    # f32/f64 inputs pin full matmul precision: the TPU MXU's default
    # drops f32 operands to bf16 mantissas, which silently degraded the
    # transform to ~1e-2 absolute error on hardware (caught by the
    # compiled-kernel parity test, tests/_hw_guards.py).  H is ±1, so
    # only the input mantissa width matters.  (A bf16_split3 chain was
    # measured SLOWER than precision="highest" here — the factor einsums
    # are layout-bound, not MXU-bound — so the simple pin stays; the
    # split pays only in the single big-GEMM paths, fjlt.py/hash.py.)
    prec = None if x.dtype == jnp.bfloat16 else "highest"
    for i, c in enumerate(chunks):
        H = jnp.asarray(_hadamard(c), x.dtype)
        in_sub = lead_l + fac_l + trail_l
        out_sub = in_sub.replace(fac_l[i], "z")
        x = jnp.einsum(
            f"{in_sub},z{fac_l[i]}->{out_sub}", x, H, precision=prec
        )
    x = x.reshape(*lead, n, *trail)
    return x * jnp.asarray(1.0 / np.sqrt(n), x.dtype)


def dct(x, axis: int = 0):
    """Orthonormal DCT-II (≙ FFTW ``REDFT10`` with ortho scaling,
    ``utility/fft/fftw_futs.h:118-126``)."""
    return jfft.dct(x, type=2, norm="ortho", axis=axis)


_FUTS = {"wht": wht, "dct": dct}


def get_fut(name: str):
    if name not in _FUTS:
        raise ValueError(f"unknown FUT {name!r}; known: {sorted(_FUTS)}")
    return _FUTS[name]


class RFUT(SketchTransform):
    """Randomized fast unitary transform: X → F·(D ⊙ X), D a random
    diagonal (default Rademacher).

    ≙ ``RFUT_t`` (``sketch/RFUT.hpp:17``): the mixing building block of
    FJLT and Fastfood.  For the WHT backend with non-power-of-2 N the
    input is zero-padded to ``next_pow2(N)``, so S = the padded size; the
    DCT backend keeps S = N exactly (the reference's FFTW path).

    Not in the string-typed registry: like the reference's C API (16
    types, ``capi/csketch.cpp:15-58``), RFUT is a building block, not a
    standalone sketch — and its (n, context) signature differs from the
    factory's (n, s, context).
    """

    sketch_type = "RFUT"
    diag_dist = "rademacher"

    def __init__(
        self, n: int, context: SketchContext, fut: str = "wht"
    ):
        self._fut_name = fut
        self._nb = next_pow2(n) if fut == "wht" else n
        super().__init__(n, self._nb, context)
        self._seed = context.seed
        self._d_base = context.reserve(n)

    def diagonal(self, dtype=jnp.float32):
        return sample(self.diag_dist, self._seed, self._d_base, self.n, dtype=dtype)

    def apply(self, A, dim: Dimension | str = Dimension.COLUMNWISE):
        dim = Dimension.of(dim)
        A = jnp.asarray(A)
        if not jnp.issubdtype(A.dtype, jnp.floating):
            A = A.astype(jnp.float32)
        squeeze = A.ndim == 1
        if squeeze:
            A = A[:, None] if dim is Dimension.COLUMNWISE else A[None, :]
        axis = 0 if dim is Dimension.COLUMNWISE else A.ndim - 1
        if A.shape[axis] != self.n:
            raise ValueError(
                f"{dim.value} apply needs {self.n} on axis {axis}, got {A.shape}"
            )
        D = self.diagonal(A.dtype)
        shape = [1] * A.ndim
        shape[axis] = self.n
        X = A * D.reshape(shape)
        if self._nb != self.n:
            pad = [(0, 0)] * A.ndim
            pad[axis] = (0, self._nb - self.n)
            X = jnp.pad(X, pad)
        out = get_fut(self._fut_name)(X, axis=axis)
        if squeeze:
            out = out[:, 0] if dim is Dimension.COLUMNWISE else out[0]
        return out

    def _param_dict(self):
        return {"fut": self._fut_name}

    @classmethod
    def _from_param_dict(cls, d, context):
        return cls(d["N"], context, fut=d.get("fut", "wht"))
