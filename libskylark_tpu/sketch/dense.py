"""Dense counter-based sketches: JLT, CT, and the lazy dense-transform engine.

Re-design of the reference's dense_transform machinery
(``sketch/dense_transform_data.hpp:22-152`` + the ~13
``dense_transform_Elemental_*.hpp`` apply specializations): the sketch
matrix ``Omega`` (shape (S, N)) is *never stored and never communicated* —
any window of it is a pure function of ``(seed, base_counter, i, j)``
(reference invariant P5, ``base/randgen.hpp:98-115``).  Here that is
``core.random.sample_window``; entry (i, j) uses counter
``base + i*N + j`` (row-major over the logical (S, N) matrix).

Distribution-aware apply specializations collapse to a single einsum:
under ``jit``/GSPMD the window generation is elementwise over an iota, so
XLA shards Omega's generation to match whatever sharding the matmul wants,
and the communication schedule (reduce-scatter within mesh rows/cols ≙
``dense_transform_Elemental_mc_mr.hpp:179,302,599``; communication-free for
the replicated-axis case ≙ ``doc/sphinx/sketching.rst:104-118``) is chosen
by the compiler.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import sparse as jsparse

from ..core.context import SketchContext
from ..core.random import sample_window
from ..utils.exceptions import UnsupportedError
from .base import Dimension, SketchTransform, register_sketch

__all__ = ["DenseSketch", "JLT", "CT", "MAX_REALIZE_ELEMENTS"]

# Above this many Omega entries, apply() switches to panel-blocked
# accumulation so the realized window stays bounded (≙ the reference's
# panel-blocked GEMM with sketch_params block-size knobs,
# ``sketch/dense_transform_Elemental_mc_mr.hpp:87-120``): Omega is
# realized panel-by-panel along N and accumulated, never materialized
# whole.  128M entries ≈ 0.5 GB in f32.
MAX_REALIZE_ELEMENTS = 1 << 27


class DenseSketch(SketchTransform):
    """Sketch with iid entries ``scale * dist()`` — the dense engine.

    ``dist`` is a key of ``core.random.DISTRIBUTIONS``; ``scale`` is a
    deterministic scalar (e.g. 1/sqrt(S) for JLT).
    """

    dist: str = "normal"

    def __init__(
        self,
        n: int,
        s: int,
        context: SketchContext,
        scale: float = 1.0,
        dist_params: dict[str, Any] | None = None,
    ):
        super().__init__(n, s, context)
        self.scale = float(scale)
        self._dist_params = dict(dist_params or {})
        self._seed = context.seed
        # ≙ context.allocate_random_samples_array(N*S) (base/context.hpp:94-101)
        self._base = context.reserve(n * s)

    # -- lazy realization (≙ realize_matrix_view) ---------------------------

    def realize(
        self,
        dtype=jnp.float32,
        offset: tuple[int, int] = (0, 0),
        shape: tuple[int, int] | None = None,
    ):
        """Materialize a window of the logical (S, N) sketch matrix.

        Any window is bit-identical to the corresponding slice of the full
        matrix (shard-local realization, ``dense_transform_data.hpp:79-152``).
        """
        w = sample_window(
            self.dist,
            self._seed,
            self._base,
            (self.s, self.n),
            dtype=dtype,
            offset=offset,
            shape=shape,
            **self._dist_params,
        )
        return w * jnp.asarray(self.scale, dtype)

    # -- apply --------------------------------------------------------------

    def _product_dtype(self, dtype):
        """Result dtype of the Omega product for operands in ``dtype``;
        None is the operands' own (every linear sketch returns what it
        was given).  A consumer with a pointwise epilogue of its own
        (``rft._Underlying``) asks for the f32 accumulator instead."""
        return None

    def apply(self, A, dim: Dimension | str = Dimension.COLUMNWISE):
        return self._apply_impl(A, Dimension.of(dim), omega=None)

    def _apply_slice_columnwise(self, A_block, start: int):
        """Partial product of the Omega column window [start, start+k):
        realized directly from the counter stream (P5 — any window is
        bit-identical to the same slice of the full matrix), so streaming
        over row blocks never materializes more than one (S, k) window."""
        k = A_block.shape[0]
        dtype = A_block.dtype
        if not jnp.issubdtype(dtype, jnp.floating):
            dtype = jnp.float32
        w = self.realize(dtype, offset=(0, start), shape=(self.s, k))
        out = self._product_dtype(dtype)
        if hasattr(A_block, "todense"):
            return _matmul(w, A_block, out)
        return _matmul(w, A_block.astype(dtype), out)

    supports_slice_kernel = True

    def apply_slice_kernel(self, A_block, start):
        """jit-safe COLUMNWISE partial with TRACED ``start`` (the P5
        counter window addresses traced offsets exactly); columns past
        the sketch domain are zeroed so a bucket-padded block overruns
        N with contribution exactly 0 (the out-of-domain stream could
        hold non-finite draws — inf·0 would poison the sum)."""
        k = A_block.shape[0]
        dtype = A_block.dtype
        if not jnp.issubdtype(dtype, jnp.floating):
            dtype = jnp.float32
        w = self.realize(dtype, offset=(0, start), shape=(self.s, k))
        valid = start + jnp.arange(k, dtype=jnp.int32) < self.n
        w = jnp.where(valid[None, :], w, jnp.zeros((), dtype))
        return _matmul(w, A_block.astype(dtype), self._product_dtype(dtype))

    def hoistable_operands(self, dtype):
        """The realized (S, N) Omega, for streaming consumers to hoist
        out of panel loops (see SketchTransform.hoistable_operands);
        None on the panel-blocked path (no single realized Omega).
        Memoized per dtype — sketches are immutable, so the realization
        never invalidates.  Mid-trace calls (the streaming-KRR chunk
        programs realize W inside their own jit) skip the cache both
        ways: a cached concrete Omega returned into a trace would be
        baked into the caller's executable as a constant."""
        if self.n * self.s > MAX_REALIZE_ELEMENTS:
            return None
        dtype = jnp.dtype(dtype)
        if not jnp.issubdtype(dtype, jnp.floating):
            dtype = jnp.dtype(jnp.float32)
        return self._memoized_operand(
            dtype.name, lambda: self.realize(dtype)
        )

    def apply_with_operands(
        self, ops, A, dim: Dimension | str = Dimension.COLUMNWISE
    ):
        return self._apply_impl(A, Dimension.of(dim), omega=ops)

    def _apply_impl(self, A, dim: Dimension, omega):
        """One implementation behind apply / apply_with_operands: same
        coercion, validation, and matmul dispatch, with ``omega``
        optionally pre-realized (bit-identical either way — realize is a
        pure function of the counter stream)."""
        A = jnp.asarray(A) if not hasattr(A, "todense") else A
        dtype = A.dtype
        if not jnp.issubdtype(dtype, jnp.floating):
            dtype = jnp.float32
        if dim is Dimension.COLUMNWISE:
            if A.shape[0] != self.n:
                raise ValueError(
                    f"columnwise apply needs A with {self.n} rows, "
                    f"got {A.shape}"
                )
        elif A.shape[-1] != self.n:
            raise ValueError(
                f"rowwise apply needs A with {self.n} columns, got {A.shape}"
            )
        if omega is None:
            if self.n * self.s > MAX_REALIZE_ELEMENTS:
                if hasattr(A, "todense"):
                    raise UnsupportedError(
                        f"dense sketch of a sparse input needs the full "
                        f"({self.s}, {self.n}) Omega materialized "
                        f"(> MAX_REALIZE_ELEMENTS); use an input-sparsity "
                        f"sketch (CWT/SJLT) at this scale"
                    )
                return self._apply_blocked(A, dim, dtype)
            # The barrier keeps the counter-stream generator out of the
            # matmul's operand fusion: fused, the v5e compiler took
            # 70-90 s per shape on realize + matmul + an elementwise
            # epilogue (4 s with Omega materialized first, as the eager
            # apply does anyway).
            omega = jax.lax.optimization_barrier(self.realize(dtype))
        elif omega.dtype != dtype:
            # Dtype-mismatched hoist: re-realize rather than astype — a
            # value-converted Omega (e.g. bf16-rounded then upcast) would
            # silently break the bit-identical-to-apply contract.
            omega = self.realize(dtype)
        out = self._product_dtype(dtype)
        if dim is Dimension.COLUMNWISE:
            return _matmul(omega, A, out)
        return _matmul_nt(A, omega, out)

    def _apply_blocked(self, A, dim: Dimension, dtype):
        """Panel-blocked apply: realize Omega in column panels along N and
        accumulate — peak extra memory is one (S, panel) window.  Equal
        panels run in a ``fori_loop`` (one traced body regardless of
        panel count); a ragged remainder panel is handled outside."""
        panel = max(1, MAX_REALIZE_ELEMENTS // self.s)
        nfull = self.n // panel
        rem0 = nfull * panel
        cw = dim is Dimension.COLUMNWISE
        A = A.astype(dtype)
        out_shape = (
            (self.s,) + A.shape[1:] if cw else A.shape[:-1] + (self.s,)
        )
        out = self._product_dtype(dtype)
        acc = jnp.zeros(out_shape, out or dtype)

        def body(p, acc):
            p0 = p * panel
            w = self.realize(dtype, offset=(0, p0), shape=(self.s, panel))
            if cw:
                blk = lax.dynamic_slice_in_dim(A, p0, panel, axis=0)
                return acc + _matmul(w, blk, out)
            blk = lax.dynamic_slice_in_dim(A, p0, panel, axis=A.ndim - 1)
            return acc + _matmul_nt(blk, w, out)

        if nfull:
            acc = lax.fori_loop(0, nfull, body, acc)
        if rem0 < self.n:
            pc = self.n - rem0
            w = self.realize(dtype, offset=(0, rem0), shape=(self.s, pc))
            if cw:
                acc = acc + _matmul(w, A[rem0:], out)
            else:
                acc = acc + _matmul_nt(A[..., rem0:], w, out)
        return acc


def _matmul(x, y, out_dtype=None):
    """Dense@dense or mixed dense/BCOO matmul (≙ base::Gemm dispatch).
    ``out_dtype`` is the dense product's result dtype (the f32
    accumulator of narrower operands, handed over unrounded); a BCOO
    product comes back in its operands' dtype whatever is asked."""
    if isinstance(x, jsparse.BCOO) or isinstance(y, jsparse.BCOO):
        return x @ y
    return jnp.matmul(x, y, preferred_element_type=out_dtype)


def _matmul_nt(x, w, out_dtype=None):
    """``x @ w.T``.  Asked for a wider result, the transpose rides in the
    dot's dimension numbers: XLA:CPU sums ``matmul(x, w.T)`` of bf16
    operands in another order under a ``jit`` than op by op, which the
    f32 accumulator would show and the eager ≡ planned contract forbids."""
    if out_dtype is None or isinstance(x, jsparse.BCOO):
        return _matmul(x, w.T, out_dtype)
    return lax.dot_general(
        x, w, (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=out_dtype,
    )


@register_sketch
class JLT(DenseSketch):
    """Johnson-Lindenstrauss: iid N(0, 1/S) dense sketch — l2 subspace
    embedding (≙ ``sketch/JLT_data.hpp:17-48``: normal entries, scale
    sqrt(1/S))."""

    sketch_type = "JLT"
    dist = "normal"

    def __init__(self, n: int, s: int, context: SketchContext):
        super().__init__(n, s, context, scale=(1.0 / s) ** 0.5)


@register_sketch
class CT(DenseSketch):
    """Cauchy transform: iid Cauchy entries scaled C/S — l1 embedding
    (Sohler-Woodruff; ≙ ``sketch/CT_data.hpp:20-47``: scale C/S)."""

    sketch_type = "CT"
    dist = "cauchy"

    def __init__(self, n: int, s: int, context: SketchContext, C: float = 1.0):
        self.C = float(C)
        super().__init__(n, s, context, scale=self.C / s)

    def _param_dict(self):
        return {"C": self.C}

    @classmethod
    def _from_param_dict(cls, d, context):
        return cls(d["N"], d["S"], context, C=d.get("C", 1.0))
