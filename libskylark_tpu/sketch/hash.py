"""Hash (CountSketch-family) sketches: CWT, MMT, WZT.

Re-design of the reference's hash_transform engine
(``sketch/hash_transform_data.hpp:21-104`` + the Elemental / local-sparse /
CombBLAS apply specializations, ``sketch/hash_transform_Elemental.hpp``,
``hash_transform_local_sparse.hpp``, ``hash_transform_CombBLAS.hpp``):
each input coordinate i in [0, N) is hashed to one output slot
``bucket[i] ~ U{0..S-1}`` with a random scaling ``value[i]`` (±1 for CWT,
Cauchy for MMT, signed reciprocal-exponential for WZT).  Columnwise,

    SA[r, :] = sum_{i : bucket[i] == r} value[i] * A[i, :]

Both arrays are counter-derived (two reserved blocks of N), so any shard can
compute its own slice of (bucket, value) without communication — the same
"hash arrays precomputed from the context" design as the reference, minus
the materialized std::vectors.

TPU mapping: the scatter-add becomes ``jax.ops.segment_sum`` (XLA scatter,
which GSPMD handles sharded); for BCOO sparse inputs the hash relabels
row/col indices directly and defers duplicate summation — exactly the
queue-then-finalize CSC build of ``hash_transform_local_sparse.hpp:88-152``.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import sparse as jsparse

from ..core.context import SketchContext
from ..core.precision import bf16_split3, f32_accumulable
from ..core.random import sample, sample_at
from ..core.sparse import RowSlots, row_slots
from . import pallas_window
from .base import Dimension, SketchTransform, register_sketch


def _window_mode(k: int, m: int, num_segments: int, dtype, nnz: int = 1) -> str:
    """STATIC routing decision for the windowed row scatter-add — shape,
    dtype, env and backend only, never values.  On a TPU a route this
    gate chose either compiles or raises: nothing probes and falls back
    (``tests/test_tpu_compile.py`` compiles the kernel for the chip at
    the bench shapes; ``chip_smoke.py`` runs its numeric self-check).
    Returns ``"xla"``, ``"kernel"``, or ``"interpret"``.  Because every
    input is
    static, the eager apply_slice path and the planned slice-kernel path
    of the same (shape, dtype) block resolve to the SAME branch — the
    bitwise planned≡eager contract holds by construction, whichever
    kernel wins.  ``nnz > 1`` rates the stacked (SJLT/OSNAP) launch,
    whose entry count is nnz·k.  ``SKYLARK_PALLAS_WINDOW=1`` forces the
    kernel, ``=interpret`` runs it in interpret mode (CPU tests), ``=0``
    (or ``SKYLARK_NO_PALLAS=1``) forces the XLA path."""
    mode = os.environ.get("SKYLARK_PALLAS_WINDOW", "")
    forced = mode in ("1", "interpret")
    ok = f32_accumulable(
        dtype, demote_f64=forced
    ) and pallas_window.supported(k, num_segments, m, nnz)
    if not ok or mode == "0":
        return "xla"
    if forced:
        return "interpret" if mode == "interpret" else "kernel"
    if (
        jax.default_backend() == "tpu"
        and pallas_window.worthwhile(k, num_segments, m, nnz)
    ):
        return "kernel"
    return "xla"


def _segment_sum_rows(A_block, b, v, num_segments: int, mode: str, acc=None):
    """Row scatter-add ``out[b[i], :] += v[i] * A_block[i, :]`` — the
    windowed analogue of ``jax.ops.segment_sum``, and the ONE dispatcher
    both the eager ``_apply_slice_columnwise`` and the jit-safe
    ``apply_slice_kernel`` call (with ``mode`` decided up front by
    :func:`_window_mode`), so the plans slice path and the eager path
    pick the same kernel by construction.  ``b``/``v`` may be stacked
    (nnz, k) — every hash function accumulates in ONE kernel launch (or
    one flat XLA scatter).  ``v`` must carry the caller's compute dtype
    on the XLA branch and f32 on the kernel branches (the value
    realization dtype is part of the routing decision, not of this
    function).  ``acc`` (f32, kernel modes only) folds the streaming
    accumulator add into the kernel's emit — the fused stream-chunk
    path.  Kernel output is f32; the caller casts at the boundary."""
    if mode == "xla":
        if b.ndim == 2:
            m = A_block.shape[1]
            stacked = (v[:, :, None] * A_block[None, :, :]).reshape(-1, m)
            return jax.ops.segment_sum(
                stacked, b.reshape(-1), num_segments=num_segments
            )
        return jax.ops.segment_sum(
            v[:, None] * A_block, b, num_segments=num_segments
        )
    return pallas_window.scatter_rows(
        A_block, b, v, num_segments, acc=acc,
        interpret=(mode == "interpret"),
    )

__all__ = ["HashSketch", "CWT", "MMT", "WZT", "SJLT"]


class HashSketch(SketchTransform):
    """Base engine: bucket ~ uniform_int(0, S-1), value ~ ``value_dist``.

    ``nnz`` hash functions per input coordinate generalize the engine from
    CountSketch (nnz=1) to OSNAP/SJLT (nnz>1): coordinate i contributes at
    nnz hashed slots.  The counter layout is (nnz·N indices, nnz·N values)
    — identical to the reference's two reserved blocks for nnz=1
    (``hash_transform_data.hpp:66-73``).
    """

    value_dist: str = "rademacher"

    # _apply_dense switches algorithm (one-hot matmul vs scatter) at
    # batch 16; plan bucketing must not pad a thin batch across it, or
    # the planned result would take a different (non-bit-identical)
    # code path than the eager apply of the same block.
    batch_size_gates = (16,)

    def __init__(self, n: int, s: int, context: SketchContext, nnz: int = 1):
        if nnz < 1:
            raise ValueError(f"hash sketch needs nnz >= 1, got {nnz}")
        self.nnz = int(nnz)
        super().__init__(n, s, context)
        self._seed = context.seed
        self._idx_base = context.reserve(self.nnz * n)
        self._val_base = context.reserve(self.nnz * n)

    # -- counter-derived hash arrays ---------------------------------------

    def _window(self, start, num, total):
        """(static_base_add, traced_offset, num) for a counter window.
        ``start`` may be a traced scalar (shard-dependent under
        ``shard_map``), in which case ``num`` is required — traced starts
        must stay below 2^32 (``raw_bits`` offset contract).  A
        ``(static_int, traced)`` pair splits a large window start exactly:
        the static part is folded into the 64-bit counter base, only the
        shard-local remainder is traced."""
        if isinstance(start, tuple):
            static, traced = start
            if num is None:
                raise ValueError("num is required when start is traced")
            return int(static), traced, num
        if isinstance(start, (int, np.integer)):
            return int(start), 0, (total - int(start) if num is None else num)
        if num is None:
            raise ValueError("num is required when start is traced")
        return 0, start, num

    def buckets(self, start=0, num: int | None = None):
        """bucket[i] for i in [start, start+num) of the flat (nnz·N)
        layout — shard-local computable, traced ``start`` supported."""
        static, offset, num = self._window(start, num, total=self.nnz * self.n)
        return sample(
            "uniform_int",
            self._seed,
            self._idx_base + static,
            num,
            dtype=jnp.int32,
            offset=offset,
            low=0,
            high=self.s - 1,
        )

    def values(self, dtype=jnp.float32, start=0, num: int | None = None):
        """Signed values, same flat layout and traced-``start`` support as
        :meth:`buckets`."""
        static, offset, num = self._window(start, num, total=self.nnz * self.n)
        return sample(
            self.value_dist,
            self._seed,
            self._val_base + static,
            num,
            dtype=dtype,
            offset=offset,
        )

    def buckets_at(self, idx):
        """:meth:`buckets` at the flat-layout positions ``idx`` (an integer
        array of any shape, ``h·N + i`` for hash h of coordinate i): the
        same numbers, computed from their counters, so a sparse operand's
        scattered coordinates are hashed without realizing or gathering
        from an N-long array."""
        return sample_at("uniform_int", self._seed, self._idx_base, idx,
                         dtype=jnp.int32, low=0, high=self.s - 1)

    def values_at(self, idx, dtype=jnp.float32):
        """:meth:`values` at the flat-layout positions ``idx``, as
        :meth:`buckets_at`."""
        return sample_at(self.value_dist, self._seed, self._val_base, idx,
                         dtype=dtype)

    # -- apply --------------------------------------------------------------

    def apply(
        self,
        A,
        dim: Dimension | str = Dimension.COLUMNWISE,
        *,
        dense_output: bool = False,
    ):
        """Apply the sketch.  For BCOO inputs, ``dense_output=True``
        accumulates straight into a dense result (≙ the reference's
        mixed sparse→dense apply, ``hash_transform_Mixed.hpp``) with a
        sort-free per-hash ``segment_sum`` — measured 1.2–1.6× the
        relabel+``sum_duplicates`` BCOO build at 1e7–1e8 nnz on v5e, and
        it never materializes the nnz·H relabeled triplets (whose lexsort
        OOMed SJLT nnz=4 at 1e8 input nonzeros); rowwise, a concrete
        BCOO's rows are laid out in slots and hashed by one-hot products
        (:meth:`_apply_sparse_dense_out`).  ``core.sparse.RowSlots`` are
        sketched rowwise, always into a dense result.  Dense inputs
        ignore the flag (their output is already dense)."""
        dim = Dimension.of(dim)
        if isinstance(A, RowSlots):
            return self._apply_sparse_dense_out(A, dim)
        if not isinstance(A, jsparse.BCOO):
            A = jnp.asarray(A)
        if A.ndim == 1:
            # Vectors are columns columnwise / rows rowwise (as in Gemv);
            # handled here once so dense and BCOO behave identically.
            A2 = A[:, None] if dim is Dimension.COLUMNWISE else A[None, :]
            out = self.apply(A2, dim, dense_output=dense_output)
            if isinstance(out, jsparse.BCOO):
                out = out.todense()
            return out[:, 0] if dim is Dimension.COLUMNWISE else out[0, :]
        if isinstance(A, jsparse.BCOO):
            if dense_output:
                return self._apply_sparse_dense_out(A, dim)
            return self._apply_sparse(A, dim)
        return self._apply_dense(A, dim)

    def _apply_slice_columnwise(self, A_block, start: int):
        """Partial scatter-add over the hash windows of coordinates
        [start, start+k): each hash function's (bucket, value) slice is a
        counter window (flat index ``h·N + i``), so a streaming pass
        regenerates exactly the k-coordinate slice per block — never the
        full N-length hash arrays.  BCOO blocks take the same per-hash
        ``segment_sum`` keyed through their local row indices."""
        k = A_block.shape[0]
        sparse_in = isinstance(A_block, jsparse.BCOO)
        in_dtype = A_block.data.dtype if sparse_in else A_block.dtype
        dtype = in_dtype if jnp.issubdtype(in_dtype, jnp.floating) else jnp.float32
        out = jnp.zeros((self.s, A_block.shape[1]), dtype)
        if sparse_in:
            rows, cols = A_block.indices[:, 0], A_block.indices[:, 1]
            data = A_block.data.astype(dtype)
            m = A_block.shape[1]
            for h in range(self.nnz):
                b = self.buckets(h * self.n + start, k)
                v = self.values(dtype, h * self.n + start, k)
                key = b[rows] * jnp.int32(m) + cols
                out = out + jax.ops.segment_sum(
                    data * v[rows], key, num_segments=self.s * m
                ).reshape(self.s, m)
            return out
        A_block = A_block.astype(dtype)
        mode = _window_mode(k, A_block.shape[1], self.s, dtype, self.nnz)
        if mode != "xla":
            # Stacked single launch: every hash window rides ONE kernel
            # call (the A tile streams through VMEM once for all nnz
            # hashes) — the jit slice path below builds the identical
            # stack, so planned≡eager holds for nnz>1 too.
            b = jnp.stack(
                [self.buckets(h * self.n + start, k) for h in range(self.nnz)]
            )
            v = jnp.stack(
                [
                    self.values(jnp.float32, h * self.n + start, k)
                    for h in range(self.nnz)
                ]
            )
            return _segment_sum_rows(A_block, b, v, self.s, mode).astype(dtype)
        for h in range(self.nnz):
            b = self.buckets(h * self.n + start, k)
            v = self.values(dtype, h * self.n + start, k)
            out = out + _segment_sum_rows(
                A_block, b, v, self.s, mode
            ).astype(dtype)
        return out

    supports_slice_kernel = True

    def _slice_kernel_impl(self, A_block, start, acc):
        """Shared body of :meth:`apply_slice_kernel` (``acc=None``) and
        :meth:`apply_slice_kernel_acc`: the per-hash windowed row
        scatter-add with TRACED ``start`` (the ``(static, traced)``
        window split keeps the 64-bit counter base exact) and values
        past the sketch domain zeroed — an out-of-domain counter stream
        can hold non-finite draws (WZT's 1/Exp), and inf·0 from a
        padded row would poison the sum.

        When an ``acc`` is given and the single-launch gate admits
        (f32 block and f32 accumulator, window kernel engaged — any
        nnz, since the stacked layout folds every hash into one
        launch), the accumulator add is folded into the kernel's emit —
        one launch per stream chunk, bitwise equal to the unfused
        ``acc + part`` composite (a single IEEE add of the same
        partial, so the plan layer's planned≡eager contract holds)."""
        k = A_block.shape[0]
        dtype = A_block.dtype
        if not jnp.issubdtype(dtype, jnp.floating):
            dtype = jnp.float32
        A_block = A_block.astype(dtype)
        m = A_block.shape[1]
        mode = _window_mode(k, m, self.s, dtype, self.nnz)
        valid = start + jnp.arange(k, dtype=jnp.int32) < self.n
        if mode != "xla":
            # Stacked single launch — same stack as the eager slice
            # path, so planned≡eager holds for every nnz.
            b = jnp.stack(
                [self.buckets((h * self.n, start), k) for h in range(self.nnz)]
            )
            v = jnp.stack(
                [
                    self.values(jnp.float32, (h * self.n, start), k)
                    for h in range(self.nnz)
                ]
            )
            v = jnp.where(valid[None, :], v, jnp.zeros((), jnp.float32))
            fuse = (
                acc is not None
                and dtype == jnp.float32
                and acc.dtype == jnp.float32
            )
            if fuse:
                return _segment_sum_rows(A_block, b, v, self.s, mode, acc=acc)
            out = _segment_sum_rows(A_block, b, v, self.s, mode).astype(dtype)
            if acc is not None:
                return acc + out.astype(acc.dtype)
            return out
        out = jnp.zeros((self.s, m), dtype)
        for h in range(self.nnz):
            b = self.buckets((h * self.n, start), k)
            v = self.values(dtype, (h * self.n, start), k)
            v = jnp.where(valid, v, jnp.zeros((), dtype))
            out = out + _segment_sum_rows(
                A_block, b, v, self.s, mode
            ).astype(dtype)
        if acc is not None:
            # The barrier keeps XLA from folding the add into the
            # scatter's init (scatter-into-acc sums in another order
            # than acc + scatter-into-zeros, the eager composite).
            out = jax.lax.optimization_barrier(out)
            return acc + out.astype(acc.dtype)
        return out

    def apply_slice_kernel(self, A_block, start):
        """jit-safe COLUMNWISE partial with TRACED ``start`` — the same
        per-hash windowed scatter-add as ``_apply_slice_columnwise``,
        routed through the same :func:`_segment_sum_rows` dispatcher so
        the plans slice path and the eager path pick the same kernel
        (bitwise-identical by construction)."""
        return self._slice_kernel_impl(A_block, start, None)

    def apply_slice_kernel_acc(self, acc, A_block, start):
        """Fused streaming chunk step: ``acc + apply_slice_kernel``
        folded into a single kernel launch when the gate in
        :meth:`_slice_kernel_impl` admits; the base composite (same
        bits) otherwise."""
        return self._slice_kernel_impl(A_block, start, acc)

    # Above this many (S·N) entries the materialized one-hot hashing
    # matrix no longer pays for itself; fall back to scatter-add.
    _ONEHOT_LIMIT = 1 << 27

    def _hash_matrix(self, dtype):
        """Dense (N, S) hashing matrix M with M[i, b[h,i]] += v[h,i].

        TPU note: for dense inputs the sketch is then a plain MXU matmul
        — an order of magnitude faster than XLA's scatter-add lowering,
        at the cost of the same O(S·N) window memory a dense sketch uses.
        Built by broadcast-compare (vectorized one-hot on the VPU) rather
        than scatter, which on TPU costs more than the matmul itself.
        BCOO inputs keep the scatter path (input-sparsity time).
        """
        b = self.buckets().reshape(self.nnz, self.n)
        v = self.values(dtype).reshape(self.nnz, self.n)
        iota = jnp.arange(self.s, dtype=b.dtype)
        M = jnp.zeros((self.n, self.s), dtype)
        for h in range(self.nnz):
            M = M + jnp.where(
                b[h][:, None] == iota[None, :], v[h][:, None], jnp.zeros((), dtype)
            )
        return M

    def _sign_scale(self):
        """Scalar c such that the hash matrix is ``c · M_int`` with
        small-integer entries (collision counts with signs) — exact in
        bf16 — or None when the values aren't sign-structured.  Lets the
        one-hot matmul ride the bf16 MXU at full precision."""
        if self.value_dist != "rademacher":
            return None
        return 1.0

    def _apply_dense(self, A, dim: Dimension):
        dtype = A.dtype if jnp.issubdtype(A.dtype, jnp.floating) else jnp.float32
        if dim is Dimension.COLUMNWISE:
            if A.shape[0] != self.n:
                raise ValueError(
                    f"columnwise apply needs A with {self.n} rows, got {A.shape}"
                )
        elif A.shape[-1] != self.n:
            raise ValueError(
                f"rowwise apply needs A with {self.n} columns, got {A.shape}"
            )
        # One-hot matmul only pays when the O(N·S) matrix build amortizes
        # over enough batch vectors; thin inputs keep the O(N·nnz) scatter.
        batch = A.shape[1] if dim is Dimension.COLUMNWISE else A.shape[0]
        if self.n * self.s <= self._ONEHOT_LIMIT and batch >= 16:
            c = self._sign_scale()
            if dtype in (jnp.bfloat16, jnp.float32):
                if c is not None:
                    return self._apply_onehot_bf16(A, dim, dtype, c)
                # Non-sign values (MMT Cauchy, WZT reciprocal-exp): fold
                # the value array into A — one elementwise pass — so the
                # hash matrix is PURE 0/1 (exact in bf16) and the matmul
                # rides the same bf16 MXU machinery as CWT.
                return self._apply_onehot_scaled(A, dim, dtype)
            M = self._hash_matrix(dtype)
            if dim is Dimension.COLUMNWISE:
                return M.T @ A.astype(dtype)
            return A.astype(dtype) @ M
        b = self.buckets().reshape(self.nnz, self.n)
        if dim is Dimension.COLUMNWISE:
            # The scatter-add IS the windowed row scatter, so the full
            # dense apply rides the same dispatcher (and the same Pallas
            # kernel, when engaged) as the streaming slices; nnz>1
            # stacks every hash function into one launch.
            mode = _window_mode(self.n, A.shape[1], self.s, dtype, self.nnz)
            if mode != "xla":
                v = self.values(jnp.float32).reshape(self.nnz, self.n)
                return _segment_sum_rows(A, b, v, self.s, mode).astype(dtype)
            # SA[r, c] = Σ_{h,i: b[h,i]=r} v[h,i]·A[i, c] — one scatter-add.
            v = self.values(dtype).reshape(self.nnz, self.n)
            stacked = (v[:, :, None] * A[None, :, :]).reshape(-1, A.shape[1])
            return jax.ops.segment_sum(
                stacked, b.reshape(-1), num_segments=self.s
            )
        # ROWWISE: (A·S^T) = (S·A^T)^T — one transpose normalizes the
        # lane-axis scatter into the kernel's sublane-dynamic form, so
        # rowwise applies ride the same window kernel.
        mode = _window_mode(self.n, A.shape[0], self.s, dtype, self.nnz)
        if mode != "xla":
            v = self.values(jnp.float32).reshape(self.nnz, self.n)
            return _segment_sum_rows(
                A.astype(dtype).T, b, v, self.s, mode
            ).T.astype(dtype)
        v = self.values(dtype).reshape(self.nnz, self.n)
        stacked = (A[:, None, :] * v[None, :, :]).reshape(A.shape[0], -1)
        return jax.ops.segment_sum(
            stacked.T, b.reshape(-1), num_segments=self.s
        ).T

    def _bf16_onehot_contract(self, X, M, dim: Dimension, dtype):
        """Shared MXU scaffolding of the one-hot paths: contract X's
        n-axis with a bf16-EXACT (N, S) matrix M, f32 accumulation; f32
        X rides the 3-pass bit-mask split (astype round-trips get elided
        by XLA's excess-precision rules on TPU — core/precision.py; any
        integer input must be value-converted before the bitcast split).
        Returns f32, (S, batch) columnwise / (batch, S) rowwise."""
        contract = (
            (((0,), (0,)), ((), ()))
            if dim is Dimension.COLUMNWISE
            else (((1,), (0,)), ((), ()))
        )

        def mm(x):
            return jax.lax.dot_general(
                x, M, contract, preferred_element_type=jnp.float32
            )

        if dtype == jnp.bfloat16:
            out = mm(X.astype(jnp.bfloat16))
        else:
            hi, lo, lo2 = bf16_split3(X.astype(jnp.float32))
            out = mm(hi) + mm(lo) + mm(lo2)
        return out.T if dim is Dimension.COLUMNWISE else out

    def _sign_matrix_bf16(self, c):
        """The (N, S) integer sign matrix ·(1/c), built directly in bf16
        (entries are signed collision counts — exact): one bf16 pass
        instead of an f32 build + rescale + round + cast chain (halves
        the build's HBM traffic at CWT's 128K x 1024 bench shape)."""
        b = self.buckets().reshape(self.nnz, self.n)
        v = self.values(jnp.float32).reshape(self.nnz, self.n)
        iota = jnp.arange(self.s, dtype=b.dtype)
        Mi = jnp.zeros((self.n, self.s), jnp.bfloat16)
        for h in range(self.nnz):
            vi = jnp.round(v[h] * jnp.float32(1.0 / c)).astype(jnp.bfloat16)
            Mi = Mi + jnp.where(
                b[h][:, None] == iota[None, :],
                vi[:, None],
                jnp.zeros((), jnp.bfloat16),
            )
        return Mi

    def hoistable_operands(self, dtype):
        """The bf16-exact one-hot matrices (sign matrix for CWT/SJLT,
        per-hash (P01, v) pairs for MMT/WZT) — the O(N·S) build a
        streaming consumer should not repeat per panel visit.  Memoized
        per dtype (sketches are immutable); mid-trace calls skip the
        cache both ways — a cached concrete matrix returned into a trace
        would be baked into the caller's executable as a constant."""
        dt = jnp.dtype(dtype)
        if dt.type not in (jnp.bfloat16, jnp.float32):
            return None
        if self.n * self.s > self._ONEHOT_LIMIT:
            return None

        def build():
            c = self._sign_scale()
            if c is not None:
                return ("sign", c, self._sign_matrix_bf16(c))
            return ("scaled", self._scaled_pairs())

        return self._memoized_operand(dt.name, build)

    def _scaled_pairs(self):
        """Per-hash (0/1 bucket matrix in bf16, value row) pairs — the
        operands of the scaled-one-hot path (MMT/WZT)."""
        b = self.buckets().reshape(self.nnz, self.n)
        v = self.values(jnp.float32).reshape(self.nnz, self.n)
        iota = jnp.arange(self.s, dtype=b.dtype)
        return tuple(
            (
                jnp.where(
                    b[h][:, None] == iota[None, :],
                    jnp.ones((), jnp.bfloat16),
                    jnp.zeros((), jnp.bfloat16),
                ),
                v[h],
            )
            for h in range(self.nnz)
        )

    def _scaled_contract(self, pairs, A, dim: Dimension, dtype):
        """out = Σ_h contract(v_h ⊙ A, P01_h) — the one scaled-one-hot
        loop behind both the per-call path and the hoisted path."""
        A32 = A.astype(jnp.float32)
        out = None
        for P01, vh in pairs:
            scaled = A32 * (
                vh[:, None] if dim is Dimension.COLUMNWISE else vh[None, :]
            )
            part = self._bf16_onehot_contract(scaled, P01, dim, dtype)
            out = part if out is None else out + part
        return out.astype(dtype)

    def apply_with_operands(
        self, ops, A, dim: Dimension | str = Dimension.COLUMNWISE
    ):
        dim = Dimension.of(dim)
        if ops is None or isinstance(A, (jsparse.BCOO, RowSlots)):
            return self.apply(A, dim)
        A = jnp.asarray(A)
        if A.ndim != 2:
            return self.apply(A, dim)
        dtype = A.dtype if jnp.issubdtype(A.dtype, jnp.floating) else jnp.float32
        if dtype not in (jnp.bfloat16, jnp.float32):
            # f64/f16 take apply's full-precision matmul — the hoisted
            # bf16 operands would silently downgrade them.
            return self.apply(A, dim)
        axis = 0 if dim is Dimension.COLUMNWISE else 1
        if A.shape[axis] != self.n:
            raise ValueError(
                f"{dim.value} apply needs A with {self.n} on axis {axis}, "
                f"got {A.shape}"
            )
        if A.shape[1 - axis] < 16:
            # Thin batches take apply's scatter path — same gate, so the
            # bit-identical-to-apply contract holds everywhere.
            return self.apply(A, dim)
        if ops[0] == "sign":
            _, c, Mi = ops
            out = self._bf16_onehot_contract(A, Mi, dim, dtype)
            return (out * jnp.float32(c)).astype(dtype)
        _, pairs = ops
        return self._scaled_contract(pairs, A, dim, dtype)

    def _apply_onehot_bf16(self, A, dim: Dimension, dtype, c):
        """Sign-valued hash sketches on the bf16 MXU at full precision:
        the hash matrix is c·M_int with small-integer entries (exact in
        bf16); bf16 inputs take one matmul, f32 inputs the 3-pass split,
        ~3x the f32 matmul rate on v5e.  Same trick as FJLT's
        subsampled-Hadamard gemm (``fjlt.py``)."""
        out = self._bf16_onehot_contract(A, self._sign_matrix_bf16(c), dim, dtype)
        return (out * jnp.float32(c)).astype(dtype)

    def _apply_onehot_scaled(self, A, dim: Dimension, dtype):
        """General-valued hash sketches (MMT/WZT) on the bf16 MXU:
        ``SA = P01ᵀ·(v ⊙ A)`` columnwise (``(A ⊙ v)·P01`` rowwise) with
        P01 the 0/1 bucket matrix — exact in bf16 — and the value array
        folded into A by one elementwise pass.  f32 inputs split the
        scaled operand ``hi + lo + lo2`` (3 exact bf16 passes), which is
        *more* accurate than the old f32 matmul (whose MXU default
        silently truncated operands to bf16 mantissas) and ~3× faster.
        """
        return self._scaled_contract(self._scaled_pairs(), A, dim, dtype)

    # Dense outputs above this many elements would not fit comfortably
    # next to the input triplets on a 16 GB chip; callers beyond it keep
    # the BCOO path (or shard via parallel.collectives).
    _DENSE_OUT_LIMIT = 1 << 28

    def _apply_sparse_dense_out(self, A, dim: Dimension):
        """BCOO → dense.  Rowwise, the rows' slots (:class:`RowSlots`, as
        ``core.sparse.panels`` makes them, or laid out here from a
        concrete BCOO) are hashed by :meth:`_rows_dense_out`.  Columnwise,
        and rowwise under a trace (where no layout can be read from the
        triplets), one flat ``segment_sum`` per hash function keyed by
        the hashed destination — no concat, no sort, O(S·batch) resident
        (the sharded P6 schedules in ``parallel/collectives.py`` use the
        same kernel per shard)."""
        if isinstance(A, RowSlots):
            if dim is not Dimension.ROWWISE:
                raise ValueError("row slots are sketched rowwise")
            return self._rows_dense_out(A)
        axis = 0 if dim is Dimension.COLUMNWISE else 1
        if A.shape[axis] != self.n:
            raise ValueError(
                f"{dim.value} apply needs A with {self.n} on axis {axis}, "
                f"got {A.shape}"
            )
        batch = A.shape[1 - axis]
        if self.s * batch > self._DENSE_OUT_LIMIT:
            raise ValueError(
                f"dense_output needs S*batch <= {self._DENSE_OUT_LIMIT} "
                f"elements, got {self.s}*{batch}; use the BCOO path or a "
                "sharded schedule (parallel.collectives)"
            )
        if axis == 1 and not isinstance(A.data, jax.core.Tracer):
            return self._rows_dense_out(row_slots(A))
        dtype = (
            A.data.dtype
            if jnp.issubdtype(A.data.dtype, jnp.floating)
            else jnp.float32
        )
        data = A.data.astype(dtype)
        rows, cols = A.indices[:, 0], A.indices[:, 1]
        hashed = rows if axis == 0 else cols
        b = self.buckets().reshape(self.nnz, self.n)
        v = self.values(dtype).reshape(self.nnz, self.n)
        out = jnp.zeros((self.s * batch,), dtype)
        for h in range(self.nnz):
            if dim is Dimension.COLUMNWISE:
                key = b[h][hashed] * jnp.int32(batch) + cols
            else:
                key = rows * jnp.int32(self.s) + b[h][hashed]
            out = out + jax.ops.segment_sum(
                data * v[h][hashed], key, num_segments=self.s * batch
            )
        shape = (self.s, batch) if axis == 0 else (batch, self.s)
        return out.reshape(shape)

    def _rows_dense_out(self, X: RowSlots):
        """Rowwise dense output of rows in slots: the (m, S) bins of the
        in-place slots, plus the bins of the overflow rows added at their
        rows (a scatter of E rows, in order).  Accumulated in f32 (or the
        data's wider dtype) and returned in the data's dtype, as a dense
        apply returns its product."""
        m, n = X.shape
        if n != self.n:
            raise ValueError(f"rowwise apply needs A with {self.n} columns, got {X.shape}")
        if self.s * m > self._DENSE_OUT_LIMIT:
            raise ValueError(
                f"dense_output needs S*batch <= {self._DENSE_OUT_LIMIT} "
                f"elements, got {self.s}*{m}")
        dtype = X.dtype if jnp.issubdtype(X.dtype, jnp.floating) else jnp.float32
        out = self._slot_bins(X.data, X.cols, dtype)
        if X.xdata.shape[0]:
            extra = self._slot_bins(X.xdata, X.xcols, dtype)
            out = out.at[X.xrows].add(extra, indices_are_sorted=True)
        return out.astype(dtype)

    def _slot_bins(self, data, cols, dtype):
        """The (r, S) bins of r lines of K slots (``data``, ``cols``).
        Every slot's bucket and value come from its column's counters
        (:meth:`buckets_at`, :meth:`values_at`), never from an N-long
        array; a padding slot holds the value 0 and adds nothing wherever
        it lands.

        The bins are products, not a scatter: a bucket b is (b // L, b %
        L) with L = 128 lanes (S itself where 128 does not divide it), and
        a line's (S/L, L) bins are the product of its slots' one-hot high
        parts, times their values, with their one-hot low parts, so a
        slot costs S/L + L one-hot entries and no S-wide compare.
        ``_ROWS_TOGETHER`` lines share one product, block-diagonal in its
        lines.  A v5e made a 45,208-row, 128-slot panel's S = 4096 bins
        so in 5.3 ms, hashing included; XLA's scatter-add (a sort of the
        slots, then a sorted scatter) took 57 ms."""
        # bf16 values ride the MXU exactly; others keep their precision
        op = dtype if dtype == jnp.bfloat16 else jnp.promote_types(dtype, jnp.float32)
        acc = jnp.promote_types(dtype, jnp.float32)
        lanes = 128 if self.s % 128 == 0 else self.s
        high = self.s // lanes
        tr = self._ROWS_TOGETHER
        r, k = cols.shape
        pad = -r % tr
        cols = jnp.pad(cols, ((0, pad), (0, 0)))
        data = jnp.pad(data, ((0, pad), (0, 0)))
        # slot j of a block belongs to its line j // k; output row i of a
        # block is (line i // high, high part i % high)
        slot_row = jnp.arange(tr * k, dtype=jnp.int32) // k
        out_row = jnp.arange(tr * high, dtype=jnp.int32)
        out = None
        for h in range(self.nnz):
            at = cols + jnp.int32(h * self.n) if h else cols
            b = self.buckets_at(at).reshape(-1, tr * k)
            v = (self.values_at(at, acc) * data.astype(acc)).astype(op)
            lhs = jnp.where(
                (out_row[None, :, None] // high == slot_row[None, None, :])
                & (out_row[None, :, None] % high == (b // lanes)[:, None, :]),
                v.reshape(-1, 1, tr * k), jnp.zeros((), op))
            rhs = (b % lanes)[..., None] == jnp.arange(lanes, dtype=jnp.int32)
            part = jax.lax.dot_general(
                lhs, rhs.astype(op), (((2,), (1,)), ((0,), (0,))),
                precision=None if op == jnp.bfloat16 else jax.lax.Precision.HIGHEST,
                preferred_element_type=acc)
            out = part if out is None else out + part
        return out.reshape(r + pad, self.s)[:r]

    # Lines whose bins one product of :meth:`_slot_bins` makes together:
    # a v5e made the url cell's 53 panels of 45,210 rows x 128 slots into
    # S = 4096 bins, two levels each with the rest of a ``zr`` call, in
    # 1.97 s two lines a product and 2.05 s four.
    _ROWS_TOGETHER = 2

    def _apply_sparse(self, A: jsparse.BCOO, dim: Dimension):
        """BCOO → BCOO: relabel hashed indices per hash function, scale
        data, sum duplicates (≙ the queue-then-finalize CSC build of
        hash_transform_local_sparse.hpp:88-152)."""
        dtype = A.data.dtype
        axis = 0 if dim is Dimension.COLUMNWISE else 1
        if A.shape[axis] != self.n:
            raise ValueError(
                f"{dim.value} apply needs A with {self.n} on axis {axis}, "
                f"got {A.shape}"
            )
        b = self.buckets().reshape(self.nnz, self.n)
        v = self.values(dtype).reshape(self.nnz, self.n)
        hashed = A.indices[:, axis]
        idx_parts, data_parts = [], []
        for h in range(self.nnz):
            idx_parts.append(A.indices.at[:, axis].set(b[h][hashed]))
            data_parts.append(A.data * v[h][hashed])
        new_idx = jnp.concatenate(idx_parts, axis=0)
        new_data = jnp.concatenate(data_parts, axis=0)
        shape = (
            (self.s, A.shape[1]) if axis == 0 else (A.shape[0], self.s)
        )
        out = jsparse.BCOO((new_data, new_idx), shape=shape)
        return out.sum_duplicates(nse=min(out.nse, shape[0] * shape[1]))


@register_sketch
class CWT(HashSketch):
    """Clarkson-Woodruff (CountSketch, OSNAP s=1): bucket + Rademacher sign —
    l2 embedding in input-sparsity time (≙ ``sketch/CWT_data.hpp:23-42``)."""

    sketch_type = "CWT"
    value_dist = "rademacher"


@register_sketch
class SJLT(HashSketch):
    """Sparse JLT / OSNAP with ``nnz`` nonzeros per column: coordinate i
    contributes ±1/√nnz at nnz hashed output slots.

    ≙ python-skylark's pure-Python SJLT (``python-skylark/skylark/
    sketch.py``, not in the C API); CWT is the nnz=1, unscaled special
    case of the same hash engine.
    """

    sketch_type = "SJLT"
    value_dist = "rademacher"

    def __init__(self, n: int, s: int, context: SketchContext, nnz: int = 4):
        super().__init__(n, s, context, nnz=nnz)

    def values(self, dtype=jnp.float32, start: int = 0, num: int | None = None):
        v = super().values(dtype, start, num)
        return v / jnp.sqrt(jnp.asarray(float(self.nnz), dtype))

    def values_at(self, idx, dtype=jnp.float32):
        v = super().values_at(idx, dtype)
        return v / jnp.sqrt(jnp.asarray(float(self.nnz), dtype))

    def _sign_scale(self):
        return 1.0 / float(np.sqrt(self.nnz))

    def _param_dict(self):
        return {"nnz": self.nnz}

    @classmethod
    def _from_param_dict(cls, d, context):
        return cls(d["N"], d["S"], context, nnz=d.get("nnz", 4))


@register_sketch
class MMT(HashSketch):
    """Meng-Mahoney: bucket + Cauchy values — l1 embedding
    (≙ ``sketch/MMT_data.hpp:21-44``)."""

    sketch_type = "MMT"
    value_dist = "cauchy"


@register_sketch
class WZT(HashSketch):
    """Woodruff-Zhang: bucket + signed reciprocal-exponential values — lp
    embedding, 1 <= p <= 2 (≙ ``sketch/WZT_data.hpp:45-127``: value =
    ±(1/Exp)^(1/p), an extra Rademacher block of N reserved after the base
    two)."""

    sketch_type = "WZT"
    value_dist = "exponential"

    def __init__(self, n: int, s: int, context: SketchContext, p: float = 2.0):
        if not 1.0 <= p <= 2.0:
            raise ValueError(f"WZT parameter p must be in [1, 2], got {p}")
        self.p = float(p)
        super().__init__(n, s, context)
        self._pm_base = context.reserve(n)

    def values(self, dtype=jnp.float32, start=0, num: int | None = None):
        static, offset, num = self._window(start, num, total=self.n)
        e = sample(
            "exponential", self._seed, self._val_base + static, num,
            dtype=dtype, offset=offset,
        )
        pm = sample(
            "rademacher", self._seed, self._pm_base + static, num,
            dtype=dtype, offset=offset,
        )
        return pm * (1.0 / e) ** jnp.asarray(1.0 / self.p, dtype)

    def values_at(self, idx, dtype=jnp.float32):
        e = sample_at("exponential", self._seed, self._val_base, idx, dtype=dtype)
        pm = sample_at("rademacher", self._seed, self._pm_base, idx, dtype=dtype)
        return pm * (1.0 / e) ** jnp.asarray(1.0 / self.p, dtype)

    def _param_dict(self):
        return {"P": self.p}

    @classmethod
    def _from_param_dict(cls, d, context):
        return cls(d["N"], d["S"], context, p=d.get("P", 2.0))
