"""Fast Johnson-Lindenstrauss transform (FJLT).

≙ ``sketch/FJLT_data.hpp:19-95`` + ``sketch/FJLT_Elemental.hpp``:
D (Rademacher diagonal) → fast unitary transform → uniform row sample with
rescale.  Counter budget matches the reference's build order: N for the
RFUT diagonal, then S for the sample indices
(``FJLT_data.hpp:80-86``).

TPU mapping (≙ the ``[VC,*] → [*,*]`` redistribute + local-FUT plan of
``FJLT_Elemental.hpp:144-186``): under GSPMD the FUT along the sketched
axis wants that axis unsharded; XLA inserts the all-to-all the reference
hand-codes as an Elemental redistribution.  Sampling and scaling are
elementwise/gather — local.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from ..core.context import SketchContext
from ..core.precision import bf16_split3
from .base import Dimension, SketchTransform, register_sketch
from .fut import RFUT
from .sampling import UST
from . import pallas_window

__all__ = ["FJLT"]


def _use_pallas() -> bool:
    return (
        os.environ.get("SKYLARK_NO_PALLAS", "0") != "1"
        and jax.default_backend() == "tpu"
    )


def _gather_mode(nrows: int, s: int, m: int, dtype) -> str:
    """STATIC routing for the sampled-transform epilogue gather — shape,
    dtype, env and backend only, never values (the ``hash._window_mode``
    discipline: planned≡eager holds by construction, and on a TPU a
    chosen route compiles or raises).  f32 only: the full-source VMEM
    tile is padded to the f32 (8, 128) grain.  ``SKYLARK_PALLAS_GATHER=1`` forces the kernel,
    ``=interpret`` runs it in interpret mode (CPU tests), ``=0`` forces
    XLA."""
    mode = os.environ.get("SKYLARK_PALLAS_GATHER", "")
    forced = mode in ("1", "interpret")
    ok = (
        jnp.dtype(dtype) == jnp.float32
        and pallas_window.supported_gather(nrows, s, m)
    )
    if not ok or mode == "0":
        return "xla"
    if forced:
        return "interpret" if mode == "interpret" else "kernel"
    if (
        jax.default_backend() == "tpu"
        and pallas_window.worthwhile_gather(nrows, s, m)
    ):
        return "kernel"
    return "xla"


# Effective MXU flops-per-HBM-byte at which the explicit subsampled-
# Hadamard matmul overtakes the streamed WHT + lane gather, per matmul
# dtype (measured on v5e: the gather runs far below streaming bandwidth,
# so the crossover favors the matmul strongly for bf16).  f32 inputs ride
# a THREE-PASS bf16 SPLIT (A = hi + lo + lo2 exactly; G is ±1 — exact in
# bf16 — so each pass is an exact selection-and-accumulate in f32 and the
# sum reproduces full f32 precision): 3 bf16 matmuls at ~95% MFU beat
# both the 6-pass f32 matmul and the WHT+gather path.  Thresholds per
# bf16-equivalent pass.
_GEMM_FPB = {
    jnp.bfloat16: 500.0,
    jnp.float32: 500.0 / 3.0,
    jnp.float64: 80.0,  # CPU parity runs: exact matmul, old gate
}
# Element cap on the realized (n, S) ±1 matrix: its transient (plus the
# int32 popcount broadcast) must stay far below HBM capacity — beyond
# this the streamed WHT path is used regardless of the flops gate
# (the gate models flops-per-byte only and could transiently allocate
# ~1 GB at n=128K, S=1024).
_GEMM_MAX_ELEMENTS = 64 << 20  # 64M entries ≈ 256 MB of int32 transient


@register_sketch
class FJLT(SketchTransform):
    """S·F·D: sample S coordinates of a randomized fast unitary transform.

    With the (orthonormal) FUT the sampled coordinates are rescaled by
    ``sqrt(NB/S)`` so that E‖sketch‖² = ‖x‖² (the reference's
    ``sqrt(N/S)``, ``FJLT_Elemental.hpp:160``, with NB the padded size).
    """

    sketch_type = "FJLT"

    def __init__(self, n: int, s: int, context: SketchContext, fut: str = "wht"):
        super().__init__(n, s, context)
        self._fut_name = fut
        # Counter layout ≙ FJLT_data_t::build: RFUT diagonal (N), then the
        # S sample indices — here a composed UST over the padded space.
        self._rfut = RFUT(n, context, fut=fut)
        self._nb = self._rfut._nb
        self._ust = UST(self._nb, s, context, replace=True)

    @property
    def sample_indices(self):
        """S uniform coordinates in [0, NB) (with replacement)."""
        return self._ust.samples

    def apply(self, A, dim: Dimension | str = Dimension.COLUMNWISE):
        dim = Dimension.of(dim)
        if self._fut_name == "wht" and not hasattr(A, "todense"):
            A2 = jnp.asarray(A)
            if A2.ndim == 2 and jnp.issubdtype(A2.dtype, jnp.floating):
                rowwise = dim is Dimension.ROWWISE
                sk_axis = 1 if rowwise else 0
                if A2.shape[sk_axis] == self.n and self._gemm_wins(A2.dtype):
                    return self._apply_srht_gemm(A2, rowwise)
            if (
                A2.ndim == 2
                and A2.dtype in (jnp.float32, jnp.bfloat16)
                and _use_pallas()
            ):
                from . import pallas_fut

                # Normalize to rowwise: columnwise = transpose in/out (two
                # extra passes; the fused kernel saves more than that vs
                # the XLA WHT lowering).  Gate on A2's dims before forming
                # the transpose so a failed gate costs nothing.
                rowwise = dim is Dimension.ROWWISE
                sk_axis, batch_axis = (1, 0) if rowwise else (0, 1)
                if A2.shape[sk_axis] == self.n and pallas_fut.supported(
                    A2.shape[batch_axis], self.n, self._nb
                ):
                    out = self._apply_pallas(A2 if rowwise else A2.T)
                    return out if rowwise else out.T
        T = self._rfut.apply(A, dim)
        scale = jnp.asarray(np.sqrt(self._nb / self.s), T.dtype)
        if (
            dim is Dimension.COLUMNWISE
            and not hasattr(T, "todense")
            and getattr(T, "ndim", 0) == 2
        ):
            # Sampled-transform epilogue: ``scale * T[idx, :]`` is a row
            # (sublane) gather — the window module's scaled-copy kernel
            # serves it bitwise-identically to XLA (pure selection plus
            # the same elementwise multiply).  Rowwise sampling gathers
            # along lanes, where XLA already wins — it stays put.
            gmode = _gather_mode(T.shape[0], self.s, T.shape[1], T.dtype)
            if gmode != "xla":
                return pallas_window.gather_scaled_rows(
                    T, self.sample_indices, scale,
                    interpret=(gmode == "interpret"),
                )
        return scale * self._ust.apply(T, dim)

    def _gemm_wins(self, dtype) -> bool:
        """Gate for the subsampled-Hadamard-as-matmul path: per input
        row/column the streamed WHT + gather moves ~(n + 2·NB + S)
        itemsize bytes of HBM while the matmul does 2·n·S flops (per
        bf16-equivalent pass — f32 runs the 3-pass bf16 split), so the
        matmul wins whenever its flop/byte ratio stays under the dtype's
        effective MXU-to-bandwidth ratio (``_GEMM_FPB``).  The realized
        ±1 matrix is additionally capped at ``_GEMM_MAX_ELEMENTS``."""
        if os.environ.get("SKYLARK_NO_SRHT_GEMM", "0") == "1":
            return False
        if self.n * self.s > _GEMM_MAX_ELEMENTS:
            return False
        fpb = _GEMM_FPB.get(jnp.dtype(dtype).type)
        if fpb is None:
            # Unknown float dtypes route to the exact precision="highest"
            # matmul branch in _apply_srht_gemm, so gate them at the
            # exact-matmul rate (f64's), not the bf16-split rate.
            fpb = _GEMM_FPB[jnp.float64]
        itemsize = jnp.dtype(dtype).itemsize
        return 2.0 * self.n * self.s <= fpb * itemsize * (
            self.n + 2 * self._nb + self.s
        )

    def _srht_matrix(self, dtype):
        """(n, S) matrix G with G[j, i] = D[j]·(-1)^popcount(j & r_i):
        the S sampled columns of H_NB restricted to the first n rows (the
        padding rows multiply zeros), with the Rademacher diagonal folded
        in.  Entries are ±1 — exact in bf16 — so the 1/√S · √(NB/NB)
        normalization is applied *after* the matmul in f32."""
        idx = self.sample_indices  # (S,) in [0, NB)
        j = jnp.arange(self.n, dtype=jnp.int32)
        bits = jax.lax.population_count(j[:, None] & idx[None, :])
        signs = (1 - 2 * (bits & 1)).astype(dtype)
        return self._rfut.diagonal(dtype)[:, None] * signs

    def _apply_srht_gemm(self, A2, rowwise: bool, G16=None):
        """out = scale · (sampled WHT columns of A ⊙ D) as dense matmul —
        same values as the WHT+gather path (same samples, same diagonal),
        chosen by :meth:`_gemm_wins` when S is small enough that the
        matmul beats the streamed transform + lane gather.

        bf16 inputs: ONE bf16 matmul (G is ±1, exact).  f32/f64 inputs:
        a 3-pass bf16 SPLIT — ``A = hi + lo + lo2`` with each part the
        bf16 rounding of the running residual (the split is exact; 8+8+8
        leading mantissa bits cover f32's 24) — so each pass is an exact
        ±select-and-f32-accumulate and the summed result carries full
        input precision at bf16 MXU rate (~3x faster than the 6-pass f32
        matmul the round-1 gate priced, and ~2x the WHT+gather path)."""
        dtype = A2.dtype
        acc = jnp.promote_types(dtype, jnp.float32)
        contract = (((1,), (0,)), ((), ())) if rowwise else (((0,), (0,)), ((), ()))

        def mm(x, g):
            args = (x, g) if rowwise else (g, x)
            return jax.lax.dot_general(
                *args, contract, preferred_element_type=acc
            )

        if dtype == jnp.bfloat16:
            out = mm(A2, G16 if G16 is not None else self._srht_matrix(dtype))
        elif dtype == jnp.float32:
            if G16 is None:
                G16 = self._srht_matrix(jnp.bfloat16)  # ±1: exact in bf16
            # Bit-mask split (NOT astype round-trips — XLA's excess-
            # precision rules elide f32→bf16→f32 convert pairs, which
            # zeroed lo/lo2 on hardware; see core/precision.py).
            hi, lo, lo2 = bf16_split3(A2)
            out = mm(hi, G16) + mm(lo, G16) + mm(lo2, G16)
        else:  # f64 (CPU parity): exact full-precision matmul
            out = jax.lax.dot_general(
                *((A2, self._srht_matrix(dtype)) if rowwise
                  else (self._srht_matrix(dtype), A2)),
                contract,
                precision="highest",
                preferred_element_type=acc,
            )
        # orthonormal WHT (1/√NB) × sample rescale √(NB/S) = 1/√S.
        return (out * acc.type(1.0 / np.sqrt(self.s))).astype(dtype)

    def hoistable_operands(self, dtype):
        """The (n, S) ±1 subsampled-Hadamard matrix (bf16 — exact), the
        expensive-to-rebuild operand of the SRHT-gemm path.  One matrix
        serves both bf16 and f32 inputs (f32 rides the 3-pass split
        against it)."""
        dt = jnp.dtype(dtype)
        if dt.type not in (jnp.bfloat16, jnp.float32):
            return None  # f64 keeps the exact paths
        if self._fut_name != "wht" or not self._gemm_wins(dt.type):
            # apply_with_operands would fall back to the streamed path —
            # don't realize a dead (n, S) matrix (it can reach 128 MB+).
            return None
        return self._srht_matrix(jnp.bfloat16)

    def apply_with_operands(
        self, ops, A, dim: Dimension | str = Dimension.COLUMNWISE
    ):
        dim = Dimension.of(dim)
        if ops is None or hasattr(A, "todense"):
            return self.apply(A, dim)
        A = jnp.asarray(A)
        if A.ndim != 2 or A.dtype not in (jnp.bfloat16, jnp.float32):
            return self.apply(A, dim)
        if not self._gemm_wins(A.dtype):
            # Per-apply flops still favor the streamed WHT (the hoist
            # only amortizes the matrix BUILD, which the gate never
            # priced) — keep the gate's verdict.
            return self.apply(A, dim)
        rowwise = dim is Dimension.ROWWISE
        if A.shape[1 if rowwise else 0] != self.n:
            raise ValueError(
                f"{dim.value} apply needs {self.n} on the sketched axis, "
                f"got {A.shape}"
            )
        return self._apply_srht_gemm(A, rowwise, G16=ops)

    def _apply_pallas(self, A, interpret: bool = False):
        """Fused one-pass D·x → WHT kernel (natural order, matching the
        XLA path): the full (m, NB) transform is written and the usual
        XLA sampled gather and rescale follow."""
        from . import pallas_fut

        if not jnp.issubdtype(A.dtype, jnp.floating):
            A = A.astype(jnp.float32)
        D = self._rfut.diagonal(A.dtype)
        T = pallas_fut.rfut_rowwise(A, D, self._nb, interpret=interpret)
        scale = jnp.asarray(np.sqrt(self._nb / self.s), T.dtype)
        return scale * self._ust.apply(T, Dimension.ROWWISE)

    def _param_dict(self):
        return {"fut": self._fut_name}

    @classmethod
    def _from_param_dict(cls, d, context):
        return cls(d["N"], d["S"], context, fut=d.get("fut", "wht"))
