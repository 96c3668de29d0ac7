"""Kernel ridge regression — the five solver strategies of ``ml/krr.hpp``.

1. ``kernel_ridge``: exact — Gram + Cholesky solve (≙ ``KernelRidge``,
   krr.hpp:49-92).
2. ``approximate_kernel_ridge``: feature map + ridge solve in feature
   space (≙ ``ApproximateKernelRidge``, krr.hpp:94-197).
3. ``sketched_approximate_kernel_ridge``: additionally sketches the
   feature-space ridge problem down to t rows (≙
   ``SketchedApproximateKernelRidge``, krr.hpp:199-310).
4. ``faster_kernel_ridge``: CG on the full Gram with the random-feature
   covariance preconditioner (≙ ``FasterKernelRidge`` +
   ``feature_map_precond_t``, krr.hpp:312-543).
5. ``large_scale_kernel_ridge``: memory-bounded block coordinate descent
   over feature-map chunks with cached Cholesky factors (≙
   ``LargeScaleKernelRidge``, krr.hpp:546-727).

Convention: X (n, d) rows-as-examples; Y (n,) or (n, t).  Feature-space
solvers return ``FeatureMapModel``; kernel-space ones ``KernelModel``.

TPU notes: Gram assembly, feature application, and the covariance HERK are
the MXU ops and shard over the examples axis; the s×s factorizations are
replicated-small (≙ the reference's ``[*,*]`` / ``[STAR,STAR]`` choices).
"""

from __future__ import annotations

import dataclasses
import types
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.experimental import sparse as jsparse
from jax.scipy.linalg import cho_factor, cho_solve, solve_triangular

from .. import guard, plans, telemetry
from ..core import sparse
from ..core.context import SketchContext
from ..core.params import Params
from ..core.precision import long_dot
from ..parallel.mesh import fully_replicated
from ..sketch.base import Dimension, create_sketch
from ..solvers.krylov import KrylovParams, cg
from ..utils import PhaseTimer, compile_cache, profiling
from .kernels import Kernel, shifted_gram
from .model import FeatureMapModel, KernelModel, _Maps

__all__ = [
    "KrrParams",
    "kernel_ridge",
    "approximate_kernel_ridge",
    "sketched_approximate_kernel_ridge",
    "faster_kernel_ridge",
    "large_scale_kernel_ridge",
    "streaming_kernel_ridge",
    "streaming_approximate_kernel_ridge",
]


@dataclass
class KrrParams(Params):
    """≙ ``krr_params_t`` (krr.hpp:8-46)."""

    use_fast: bool = False          # fast feature transforms (Fastfood)
    sketched_rr: bool = False       # sketch the feature ridge problem
    sketch_size: int = -1           # -1 → 4·s (krr.hpp:146)
    fast_sketch: bool = False       # CWT instead of FJLT for the sketch
    tolerance: float = 1e-3         # iterative tolerance
    res_print: int = 10
    iter_lim: int = 1000
    max_split: int = 0              # feature chunk size (large-scale)
    # Preemption safety (resilient.ResilientRunner over the CG path; no
    # reference counterpart — the reference is MPI fail-stop):
    checkpoint_dir: str | None = None
    checkpoint_every: int = 25      # CG iterations per checkpoint round
    resume: bool = False


def _psd_gram(A, B):
    """Gram products feeding a Cholesky run at ``precision='highest'``
    with ≥f32 OUTPUT: TPU's default f32 matmul passes through bf16,
    whose error can push ``ZᵀZ + λI`` indefinite for small λ (cho_factor
    then yields silent NaNs).  bf16 inputs keep full MXU rate — their
    products accumulate exactly in f32 — but the result must NOT round
    back to bf16 (a bf16 Gram re-introduces the same ~2e-3 hazard at the
    output; round-3 review finding), so the accumulator dtype is pinned.
    """
    acc = jnp.promote_types(A.dtype, jnp.float32)
    return jnp.dot(A, B, precision="highest", preferred_element_type=acc)


def _as2d(Y):
    Y = jnp.asarray(Y)
    return (Y[:, None], True) if Y.ndim == 1 else (Y, False)


def _dense(X):
    """Densify BCOO for Gram-matrix paths (kernel matrices are dense
    anyway); leave dense arrays untouched."""
    return X.todense() if hasattr(X, "todense") else jnp.asarray(X)


def _maybe_sparse(X):
    """Keep BCOO as-is for feature-map paths (the sketches handle it)."""
    return X if hasattr(X, "todense") else jnp.asarray(X)


def _tag(params: KrrParams) -> str:
    return "fast" if params.use_fast else "regular"


def kernel_ridge(kernel: Kernel, X, Y, lam: float, params: KrrParams | None = None):
    """Exact KRR: solve (K + λI)·A = Y; returns a ``KernelModel``."""
    params = params or KrrParams()
    X = _dense(X)
    Y2, _ = _as2d(Y)
    K = kernel.gram(X)
    n = K.shape[0]
    Kl = fully_replicated(K + lam * jnp.eye(n, dtype=K.dtype))
    A = cho_solve(cho_factor(Kl, lower=True), Y2)
    return KernelModel(kernel, X, A)


def approximate_kernel_ridge(
    kernel: Kernel,
    X,
    Y,
    lam: float,
    s: int,
    context: SketchContext,
    params: KrrParams | None = None,
):
    """Feature map Z = S(X) (n, s), then ridge: (ZᵀZ + λI)W = ZᵀY.

    ≙ ``ApproximateKernelRidge`` (krr.hpp:94-197; its ``El::Ridge`` is the
    same normal-equations solve).  Returns a ``FeatureMapModel``; under
    guarding (``SKYLARK_GUARD``, default on) a non-finite Cholesky factor
    (singular/indefinite-by-rounding regularized Gram) falls back to the
    eigh pseudoinverse solve, the coefficients pass a finiteness
    sentinel, and ``model.info["recovery"]`` records the attempts.

    Policy (``SKYLARK_POLICY``, on by default): a matured profile entry
    for this (backend, dtype, shape-class) may run the feature Gram
    bf16-first (the MXU-heavy ops; ``_psd_gram`` still accumulates
    exactly in f32), escalating back to the feature dtype when the bf16
    attempt trips the guard fallback — the decision lands in
    ``model.info["policy"]``.  With an empty store the solve is bitwise
    identical to the unrouted library.
    """
    params = params or KrrParams()
    X = _maybe_sparse(X)
    Y2, _ = _as2d(Y)
    S = kernel.create_rft(s, _tag(params), context)
    Z = plans.apply(S, X, Dimension.ROWWISE)  # (n, s)
    if params.sketched_rr:
        return _solve_sketched_ridge(S, Z, Y2, lam, s, context, params)
    # Host-side sentinel reads cannot run under an enclosing jit trace.
    guarded = guard.enabled() and not guard.is_traced(Z, Y2)
    from .. import policy

    decision = policy.consult(
        "krr",
        m=X.shape[0],
        n=int(s),
        targets=Y2.shape[1],
        dtype=Z.dtype.name,
        sparse=hasattr(X, "todense"),
        guard_on=guarded,
    )

    def ridge_solve(Zs):
        report = (
            guard.RecoveryReport(stage="approximate_krr")
            if guarded
            else guard.RecoveryReport.disabled("approximate_krr")
        )
        G = fully_replicated(
            _psd_gram(Zs.T, Zs) + lam * jnp.eye(s, dtype=Zs.dtype)
        )
        # Factor/solve in _psd_gram's ≥f32 accumulator dtype; the model's
        # coefficient dtype stays the feature dtype (API contract — bf16
        # features must not silently return an f32 model).
        c, low = cho_factor(G, lower=True)
        fellback = False
        if guarded and not guard.tree_all_finite(c):
            W = guard.pinv_psd_solve(G, Zs.T @ Y2).astype(Zs.dtype)
            report.record(
                "fallback", verdict=guard.FALLBACK,
                detail="non-finite Cholesky factor; eigh pseudoinverse solve",
            )
            report.recovered = True
            fellback = True
        else:
            W = cho_solve((c, low), Zs.T @ Y2).astype(Zs.dtype)
        if guarded:
            guard.check_finite(W, "approximate_krr", report=report)
        return W, report, fellback

    bf16_note = None
    if decision.compute_dtype == "bfloat16":
        from ..utils.exceptions import NumericalHealthError

        try:
            W, report, fellback = ridge_solve(Z.astype(jnp.bfloat16))
        except NumericalHealthError:
            W, fellback = None, True
        if fellback:
            decision.escalated = True
            bf16_note = "fail"
            W, report, _ = ridge_solve(Z)
        else:
            W = W.astype(Z.dtype)
    else:
        W, report, _ = ridge_solve(Z)
    model = FeatureMapModel([S], W)
    model.info = {"recovery": report.to_dict(), "policy": decision.to_dict()}
    policy.observe(decision, model.info, bf16=bf16_note)
    telemetry.run_summary("approximate_krr", model.info)
    return model


def _solve_sketched_ridge(S, Z, Y2, lam, s, context, params):
    """Sketch the (n, s) ridge problem down to t rows (krr.hpp:135-180)."""
    n = Z.shape[0]
    t = params.sketch_size if params.sketch_size != -1 else min(4 * s, n)
    sk_type = "CWT" if params.fast_sketch else "FJLT"
    R = create_sketch(sk_type, n, t, context)
    SZ = plans.apply(R, Z, Dimension.COLUMNWISE)  # (t, s)
    SY = plans.apply(R, Y2, Dimension.COLUMNWISE)  # (t, k)
    G = fully_replicated(_psd_gram(SZ.T, SZ) + lam * jnp.eye(s, dtype=Z.dtype))
    W = cho_solve(cho_factor(G, lower=True), SZ.T @ SY).astype(Z.dtype)
    return FeatureMapModel([S], W)


def sketched_approximate_kernel_ridge(
    kernel, X, Y, lam, s, context, params: KrrParams | None = None
):
    """≙ ``SketchedApproximateKernelRidge`` (krr.hpp:199-310)."""
    params = dataclasses.replace(params or KrrParams(), sketched_rr=True)
    return approximate_kernel_ridge(kernel, X, Y, lam, s, context, params)


# Columns of Ũ solved for at a time.  XLA's triangular solve keeps
# temporaries of fifteen times its right-hand side (12.3 GB for all
# 49,152 columns against a 4,096² factor, compiled for a v5e; 1.1 GB a
# block of 4,096).
_BLOCK = 4096


@jax.jit
def _woodbury_factor(Z, lam):
    """Ũ = L⁻¹Zᵀ/λ with L = chol(I + ZᵀZ/λ), for features Z (n, s): the
    Gram product, the Cholesky factorization and the triangular solve of
    the preconditioner's build as one program.  The solve goes over Ũ's
    columns a block at a time, the last block from ``n - block`` (it
    re-writes a few columns with the values they have)."""
    n, s = Z.shape
    C = fully_replicated(jnp.eye(s, dtype=Z.dtype) + long_dot(Z.T, Z) / lam)
    L = jnp.linalg.cholesky(C)
    block = min(_BLOCK, n)

    def body(i, U):
        start = jnp.minimum(i * block, n - block)
        Zb = jax.lax.dynamic_slice_in_dim(Z, start, block)
        # Solve in C's ≥f32 dtype, store Ũ back in the feature dtype —
        # the (s, n) buffer is the precond's memory footprint.
        Ub = solve_triangular(L, Zb.T.astype(C.dtype), lower=True) / lam
        return jax.lax.dynamic_update_slice_in_dim(U, Ub.astype(Z.dtype), start, 1)

    return jax.lax.fori_loop(
        0, -(-n // block), body, jnp.zeros((s, n), Z.dtype)
    )


@jax.tree_util.register_pytree_node_class
class _FeatureMapPrecond:
    """(ZᵀZ + λI)⁻¹ as a preconditioner for (K + λI), via Woodbury.

    ≙ ``feature_map_precond_t`` (krr.hpp:312-450): U = Z (s, n) features;
    C = I + U·Uᵀ/λ, L = chol(C), Ũ = L⁻¹U/λ; apply(B) = B/λ − Ũᵀ(Ũ·B).

    A registered pytree with the leaves Ũ and λ, so that it crosses
    ``jax.jit`` as an argument and CG rides ``krylov.run``'s one cached
    program.  The products with Ũ run at ``highest`` (they are two reads
    of Ũ either way, and a rounded R would make M a different operator
    every iteration), the one over n through
    ``core.precision.long_dot``: apply is a difference that has to be
    right to λ/μ of its terms, 5·10⁻⁷ along K's largest eigenvalue
    μ ≈ n/2 at λ = 0.01 and n = 5·10⁴ (upstream runs in double).
    """

    def __init__(self, U, lam):
        self.U, self.lam = U, lam

    @classmethod
    def build(cls, kernel, lam, X, s, context, params):
        with telemetry.span("faster_krr.precond.features"):
            S = kernel.create_rft(s, _tag(params), context)
            Z = plans.apply(S, jnp.asarray(X), Dimension.ROWWISE)  # (n, s)
        with telemetry.span("faster_krr.precond.factor"):
            lam = jnp.asarray(lam, Z.dtype)
            # Z dies with this frame
            return cls(profiling.launch(_woodbury_factor, Z, lam), lam)

    def apply(self, B):
        UB = long_dot(self.U, B).astype(B.dtype)
        # ŨᵀUB contracted over Ũ's rows in place: eagerly (``krylov.init``)
        # a ``.T`` is a second (s, n) array
        return B / self.lam - jax.lax.dot_general(
            self.U, UB, (((0,), (0,)), ((), ())), precision="highest"
        )

    def apply_adjoint(self, B):
        return self.apply(B)

    def tree_flatten(self):
        return (self.U, self.lam), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def faster_kernel_ridge(
    kernel: Kernel,
    X,
    Y,
    lam: float,
    s: int,
    context: SketchContext,
    params: KrrParams | None = None,
):
    """CG on (K + λI)·A = Y preconditioned by the random-feature
    covariance (≙ ``FasterKernelRidge``, krr.hpp:452-543).

    Three cached programs and CG's: the preconditioner's feature map
    (``plans.apply``), its Woodbury factor, and K + λI written once in
    row blocks (``kernels.shifted_gram``); the preconditioner first, so
    that its temporaries are gone before the n×n matrix is there.
    ``model.info`` holds CG's ``iterations``, ``flag``, ``resid`` and
    ``precond_features``.
    """
    with telemetry.span("faster_kernel_ridge"):
        params = params or KrrParams()
        X = _dense(X)
        Y2, _ = _as2d(Y)
        P = _FeatureMapPrecond.build(kernel, lam, X, s, context, params)
        with telemetry.span("faster_krr.gram"):
            Kl = profiling.launch(shifted_gram, kernel, X, lam)
        kp = KrylovParams(tolerance=params.tolerance, iter_lim=params.iter_lim)
        if params.checkpoint_dir:
            # Preemption-safe CG: everything outside the CG state (Gram,
            # preconditioner) is deterministically rebuilt from (X, context)
            # on resume, so only the Krylov carry rides the checkpoint.
            from ..resilient import ResilientParams, ResilientRunner
            from ..solvers.krylov import cg_chunked

            A, info = ResilientRunner(
                cg_chunked(Kl, Y2, precond=P, params=kp),
                ResilientParams(
                    am_i_printing=params.am_i_printing,
                    log_level=params.log_level,
                    prefix=params.prefix,
                    checkpoint_dir=params.checkpoint_dir,
                    checkpoint_every=params.checkpoint_every,
                    resume=params.resume,
                ),
            ).run()
        else:
            A, info = cg(Kl, Y2, precond=P, params=kp)
        model = KernelModel(kernel, X, A)
        model.info = {**info, "precond_features": int(s)}
        return model


def _chunk_sizes(d: int, s: int, params: KrrParams) -> list[int]:
    """Feature-chunk sizes (≙ krr.hpp:573-592) — ONE implementation
    shared by the large-scale and streaming solvers: both build their
    feature maps from the same context, so identical chunking is what
    keeps their counter streams (and trained models) interchangeable."""
    sinc = d if params.max_split == 0 else max(1, params.max_split // 2)
    sizes = []
    remains = s
    while remains > 0:
        this = remains if remains <= 2 * sinc else sinc
        sizes.append(this)
        remains -= this
    return sizes


def large_scale_kernel_ridge(
    kernel: Kernel,
    X,
    Y,
    lam: float,
    s: int,
    context: SketchContext,
    params: KrrParams | None = None,
):
    """Memory-bounded block coordinate descent over feature chunks.

    ≙ ``LargeScaleKernelRidge`` (krr.hpp:546-727): chunk the s features
    into C transforms of ~max_split/2 each; iterate
      ZR = Z_c·R − λ·W_c;  δ = (Z_cZ_cᵀ + λI)⁻¹·ZR  (cached Cholesky);
      W_c += δ;  R −= Z_cᵀ·δ
    until the relative update is below tolerance.
    """
    params = params or KrrParams()
    X = _maybe_sparse(X)
    Y2, _ = _as2d(Y)
    n, d = X.shape

    sizes = _chunk_sizes(d, s, params)
    maps = [kernel.create_rft(sz, _tag(params), context) for sz in sizes]

    # Memory-bounded by construction: each chunk's Z is recomputed from
    # its counter-based map on every sweep and never held alongside the
    # others (≙ the reference re-applying featureMaps[c] per iteration;
    # only the small per-chunk Cholesky factors are cached,
    # krr.hpp:608-660).  Peak extra memory = one (n, max chunk) block.
    def chunk_Z(c):
        # Plan-cached: every sweep re-derives this chunk's features, so
        # the fused executable compiled on sweep 1 serves all of them.
        return plans.apply(maps[c], X, Dimension.ROWWISE).T  # (sz, n)

    # First sweep builds the cached factors (krr.hpp:608-660); the first
    # chunk also establishes the feature dtype for the state arrays.
    factors = []
    Ws = None
    t = Y2.shape[1]
    Z = None
    for c in range(len(maps)):
        Z = None  # release chunk c-1 before materializing chunk c
        Z = chunk_Z(c)
        if Ws is None:
            dtype = Z.dtype
            lam_ = jnp.asarray(lam, dtype)
            Ws = [jnp.zeros((sz, t), dtype) for sz in sizes]
            R = Y2.astype(dtype)
        G = fully_replicated(
            _psd_gram(Z, Z.T) + lam_ * jnp.eye(Z.shape[0], dtype=dtype)
        )
        Lc = cho_factor(G, lower=True)
        factors.append(Lc)
        ZR = Z @ R - lam_ * Ws[c]
        # cast back: the f32 factor solve must not promote the resident
        # (n, t) R / Ws state out of the feature dtype (memory contract)
        delta = cho_solve(Lc, ZR).astype(dtype)
        Ws[c] = Ws[c] + delta
        R = R - Z.T @ delta
        # Same one-chunk memory contract as the later sweeps: block until
        # this chunk executed before dispatching (= allocating) the next.
        jax.block_until_ready(delta)

    # More sweeps (krr.hpp:668-727).  The per-chunk float() readback is a
    # deliberate host sync: under async dispatch the next chunk's (n, sz)
    # Z buffer is ALLOCATED at dispatch time, so without a sync several
    # chunks can be resident at once and the one-chunk memory contract
    # (the reason this solver exists) breaks.  At capacity scale the
    # round-trip is ~3% of a sweep — not worth trading the bound for.
    for it in range(1, params.iter_lim):
        delsize = 0.0
        for c in range(len(maps)):
            Z = None  # release chunk c-1 before materializing chunk c
            Z = chunk_Z(c)
            ZR = Z @ R - lam_ * Ws[c]
            delta = cho_solve(factors[c], ZR).astype(dtype)
            Ws[c] = Ws[c] + delta
            R = R - Z.T @ delta
            delsize += float(jnp.sum(delta * delta))
        wnorm = float(
            jnp.sqrt(sum(jnp.sum(W * W) for W in Ws))
        )
        reldel = (delsize**0.5) / max(wnorm, 1e-30)
        params.log(2, f"iteration {it}, relupdate = {reldel:.2e}")
        if reldel < params.tolerance:
            break

    W = jnp.concatenate(Ws, axis=0)
    return FeatureMapModel(maps, W)


def streaming_approximate_kernel_ridge(
    kernel: Kernel,
    source,
    lam: float,
    s: int,
    context: SketchContext,
    params: KrrParams | None = None,
    *,
    targets: int = 1,
    stream_params=None,
    fault_plan=None,
):
    """One-pass :func:`approximate_kernel_ridge` over ``(X_block,
    y_block)`` batches — X never resident.

    The normal equations accumulate per batch (``G += Z_bᵀZ_b``,
    ``c += Z_bᵀy_b`` with ``Z_b = S(X_b)`` rowwise) through the
    ``streaming`` engine, which brings the prefetch pipeline and
    checkpoint/resume (``stream_params`` — a
    :class:`~libskylark_tpu.streaming.StreamParams`) along.  Trained on
    the same ``context`` seed, the model is allclose-interchangeable
    with the in-core solver's, modulo per-batch summation order.
    ``source`` is an iterable of batches or a re-openable factory
    ``f(start_batch) -> iterator`` (``io.stream_libsvm`` /
    ``io.stream_hdf5`` wrapped in a lambda both qualify).

    Guarding (``SKYLARK_GUARD``, on by default): a batch that NaN-poisons
    the accumulators is replayed at the chunk boundary and a non-finite
    Cholesky factor reroutes to the eigh pseudoinverse solve; the guard's
    :class:`~libskylark_tpu.guard.RecoveryReport` ledger lands in
    ``model.info["recovery"]``.  ``fault_plan``
    (:class:`~libskylark_tpu.resilient.FaultPlan` with
    ``nan_at``/``bad_sketch_at`` keyed by batch index) injects the
    faults the guard recovers from.
    """
    from .. import streaming

    return streaming.kernel_ridge(
        source, kernel, lam, s, context,
        targets=targets, krr_params=params, params=stream_params,
        fault_plan=fault_plan,
    )


def panel_size(n: int, block_rows: int) -> int:
    """The rows of :func:`streaming_kernel_ridge`'s panels for ``n`` rows
    and a request of ``block_rows``: the request where it divides n,
    else the largest divisor of n not exceeding it (the panel size only
    shapes memory, not results).  A degenerate divisor (n near-prime)
    would turn the panel loops into per-row iteration: refused with an
    actionable message instead."""
    if n % block_rows == 0:
        return block_rows
    best = max(b for b in range(1, min(block_rows, n) + 1) if n % b == 0)
    # best == n is always usable (the whole problem fits in ONE panel —
    # nb=1 — the degenerate-divisor concern is moot); only error when a
    # large n truly fractures into tiny panels.
    if best < n and best < max(256, block_rows // 16):
        raise ValueError(
            f"n={n} has no usable panel divisor <= {block_rows} "
            f"(best is {best}); pad n to a composite size or pass a "
            "block_rows that divides it"
        )
    return best


def streaming_kernel_ridge(
    kernel: Kernel,
    block_fn,
    shape: tuple[int, int],
    Y,
    lam: float,
    s: int,
    context: SketchContext,
    params: KrrParams | None = None,
    block_rows: int = 262_144,
    feature_dtype=jnp.bfloat16,
    block_args: tuple = (),
    timer=None,
):
    """Row-streamed block coordinate descent: the single-chip face of the
    10M×4K north-star shape.

    ``block_args``: extra device arrays threaded into ``block_fn(start,
    rows, *block_args)`` as REAL jit arguments.  A ``block_fn`` that
    closes over a large device array instead would be embedded as a
    compile-time constant (round-tripped through the host, and part of
    the executable); counter-generated sources need none.

    The three per-chunk programs are built once a process and keyed by
    the maps' value, the sizes, ``feature_dtype`` and ``block_fn``'s
    identity, λ an operand (:func:`streaming_krr_chunk_programs`): a
    later call with a fresh kernel, context and maps of equal value, or
    with another λ, builds nothing.  That holds for a ``block_fn`` that
    lives with its module and reads its data through ``block_args``
    alone: it is traced once a process, and what else it read would be
    read then and never again.  One that closes over anything, has
    default arguments or reads data from its module's namespace gets
    programs of the call's own, built again every call.

    A sparse X: ``block_fn`` returns a BCOO panel or its rows in slots
    (``core.sparse.RowSlots``), and the maps take it as it is (a
    TensorSketch never densifies it; the operands hoisted for it are a
    sparse input's).  The X of ``core.sparse.panels`` comes with its
    ``block_fn``, ``core.sparse.panel_rows``, which lives with its
    module: ``block_fn=panel_rows, block_args=(panels(X, block_rows),)``
    with ``block_rows`` from :func:`panel_size`.

    ``model.info``: ``feature_passes``, the panel passes over X the
    call launched (a chunk's ``gram`` in sweep 0, its ``zr`` and
    ``apply_delta`` every sweep: 1 + 2·sweeps for one chunk), and
    ``feature_map``, the maps' ``sketch_type``; with ``core.sparse.
    Panels`` among ``block_args`` also ``feature_nnz``, the nonzeros
    fed to the feature maps: nonzeros × the map's levels (a
    TensorSketch's q CountSketches, else 1) × panel passes.

    ``timer``: optional ``utils.PhaseTimer`` — sweep 0 (which absorbs
    the per-chunk program compiles and factorizations) lands in phase
    ``"sweep0"``, steady sweeps in ``"sweep"`` (the ADMM solver's
    phase-timer convention; lets benchmarks read the marginal s/sweep
    without compile-cancellation tricks).

    ``large_scale_kernel_ridge`` (≙ krr.hpp:546-727) bounds memory in the
    FEATURE direction but keeps X — and each chunk's (n, sz) Z — resident;
    at 10M×4096 neither fits one chip (X alone is 80 GB in bf16).  Here
    the EXAMPLES direction streams too: ``block_fn(start_row, rows)``
    yields X row panels (jit-traceable with a traced start, like the
    streaming-SVD contract), each chunk's features are regenerated per
    panel inside a ``fori_loop``, and only O(panel·max(d, sz)) feature
    memory plus the (n, t) residual R is ever resident.  Per sweep each
    chunk makes two panel passes (accumulate ZR = Z_c·R, then apply
    R ← R − Z_cᵀ·δ) — the BCD update equations are exactly
    ``large_scale_kernel_ridge``'s.

    The reference reaches this scale by spreading X over MPI ranks
    (krr.hpp:546's Elemental [MC,MR] X); one TPU chip instead re-reads
    the counter stream / storage.  Multi-chip runs shard the panels with
    ``mesh`` machinery upstream (see ``__graft_entry__.dryrun_multichip``).
    """
    with telemetry.span("krr_train"):
        compile_cache.place()
        params = params or KrrParams()
        n, d = shape
        block_rows = panel_size(n, block_rows)
        nb = n // block_rows
        Y2, _ = _as2d(Y)
        t = Y2.shape[1]

        with telemetry.span("krr.programs"):
            sizes = _chunk_sizes(d, s, params)
            maps = [
                kernel.create_rft(sz, _tag(params), context) for sz in sizes
            ]
            by_value = _Maps(maps)
            programs = [
                streaming_krr_chunk_programs(
                    by_value, c, nb, block_rows, block_fn, feature_dtype)
                for c in range(len(maps))
            ]
            lam_ = jnp.asarray(lam, jnp.float32)
        factors = []
        Ws = [jnp.zeros((sz, t), jnp.float32) for sz in sizes]
        # Panel-major residual (see streaming_krr_chunk_programs): sharded
        # callers pay one reshard here, zero per-sweep R collectives after.
        R = Y2.astype(jnp.float32).reshape(nb, block_rows, t)

        # Without a caller's timer the phases only annotate the trace.
        timer = timer if timer is not None else PhaseTimer(sync=False)
        passes = 0  # panel passes over X launched: one a chunk program

        # Sweep 0 is unconditional (factors must exist), matching
        # large_scale_kernel_ridge's loop structure where the first sweep
        # runs outside the iteration count — iter_lim=0 means "one pass".
        for it in range(max(params.iter_lim, 1)):
            with timer.phase("sweep0" if it == 0 else "sweep") as ph:
                delsize = 0.0
                for c, (gram, zr, apply_delta) in enumerate(programs):
                    passes += 2 if it else 3
                    if it == 0:
                        with telemetry.span("krr.gram"):
                            G = gram(lam_, *block_args)
                        with telemetry.span("krr.factor"):
                            factors.append(cho_factor(G, lower=True))
                        del G
                    with telemetry.span("krr.zr"):
                        ZR = zr(lam_, R, Ws[c], *block_args)
                    with telemetry.span("krr.solve"):
                        delta = cho_solve(factors[c], ZR)
                        Ws[c] = Ws[c] + delta
                    with telemetry.span("krr.apply_delta"):
                        R = apply_delta(R, delta, *block_args)
                    with telemetry.span("krr.converge"):  # a host wait
                        delsize += float(jnp.sum(delta * delta))
                ph.result = R
            with telemetry.span("krr.converge"):
                wnorm = float(jnp.sqrt(sum(jnp.sum(W * W) for W in Ws)))
            reldel = (delsize**0.5) / max(wnorm, 1e-30)
            params.log(2, f"iteration {it}, relupdate = {reldel:.2e}")
            if it > 0 and reldel < params.tolerance:
                break

        W = jnp.concatenate(Ws, axis=0)
        model = FeatureMapModel(maps, W)
        model.info = {"feature_passes": passes,
                      "feature_map": maps[0].sketch_type}
        nse = sum(a.nse for a in block_args if isinstance(a, sparse.Panels))
        if nse:
            model.info["feature_nnz"] = nse * getattr(maps[0], "q", 1) * passes
        return model


@dataclass(frozen=True)
class _ChunkSpec:
    """What a chunk program is keyed by besides its operands' shapes:
    the maps by value, the chunk, the panel grid, the feature dtype, and
    ``block_fn`` by identity."""

    maps: _Maps
    c: int
    nb: int
    block_rows: int
    feature_dtype: Any
    block_fn: Callable

    @property
    def map(self):
        return self.maps.maps[self.c]

    @property
    def sz(self):
        return self.map.s


def _hoisted(spec, bargs):
    """The map's operands hoisted out of the panel loop; for a sparse
    panel (``block_fn`` gives a BCOO or ``core.sparse.RowSlots``, seen
    from its shapes alone) a sparse input's."""
    panel = jax.eval_shape(lambda *a: spec.block_fn(0, spec.block_rows, *a), *bargs)
    if isinstance(panel, (jsparse.BCOO, sparse.RowSlots)):
        return spec.map.hoistable_operands(spec.feature_dtype, sparse=True)
    return spec.map.hoistable_operands(spec.feature_dtype)


def _chunk_Zp(spec, start, bargs, ops):
    """(block_rows, sz) feature panel of chunk c, built in-graph.
    Natural rowwise layout: every consumer contracts it with
    ``dot_general`` directly — materializing a transpose (or an
    astype-to-f32 copy) of the panel costs ~3 extra HBM passes per
    visit, measured ~2.3 s/sweep-pass at the 10M×4096 shape.  The
    map's counter-realized operands are hoisted to ``ops`` (once per
    program, outside the panel loop): XLA does not LICM the ~11 ms
    per-visit W realization out of the fori_loop by itself."""
    with jax.named_scope("krr.features"):
        Xp = spec.block_fn(start, spec.block_rows, *bargs).astype(
            spec.feature_dtype)
        return spec.map.apply_with_operands(ops, Xp, Dimension.ROWWISE)


# All contractions consume the (block_rows, sz) panel in place via
# dot_general with an f32 preferred_element_type: bf16 panels contract
# at MXU rate with exact-f32 accumulation and are never rounded back
# (the _psd_gram hazard) nor upcast into a materialized f32 copy.
# precision='highest' pins the f32/f64 feature case.
def _prec(dtype):
    return None if dtype == jnp.bfloat16 else "highest"


def _gram(spec, lam, *bargs):
    sz = spec.sz
    ops = _hoisted(spec, bargs)

    def body(p, G):
        Zp = _chunk_Zp(spec, p * spec.block_rows, bargs, ops)
        with jax.named_scope("krr.gram_product"):
            blk = jax.lax.dot_general(
                Zp, Zp, (((0,), (0,)), ((), ())),
                precision=_prec(Zp.dtype),
                preferred_element_type=jnp.float32,
            )
            return G + blk

    G = jax.lax.fori_loop(
        0, spec.nb, body, jnp.zeros((sz, sz), jnp.float32)
    )
    return G + lam * jnp.eye(sz, dtype=jnp.float32)


# The residual travels as (nb, block_rows, t): panels on the LEADING
# (unsharded) axis, rows of each panel on the shardable middle axis.
# A traced-index slice R3[p] then never touches the sharded
# dimension, so GSPMD keeps it local — the (N, t) layout with a
# traced-offset dynamic_slice cost a full all-gather of R per sweep
# on the virtual mesh (compiled-HLO finding, round 4; the one-time
# reshard into panel-major happens outside the sweep loop).


def _zr(spec, lam, R3, Wc, *bargs):
    ops = _hoisted(spec, bargs)

    def body(p, acc):
        Zp = _chunk_Zp(spec, p * spec.block_rows, bargs, ops)
        with jax.named_scope("krr.zr_product"):
            Rp = jax.lax.dynamic_index_in_dim(R3, p, 0, keepdims=False)
            return acc + jax.lax.dot_general(
                Zp, Rp, (((0,), (0,)), ((), ())),
                precision=_prec(Zp.dtype),
                preferred_element_type=jnp.float32,
            )

    acc0 = jnp.zeros((spec.sz, R3.shape[2]), jnp.float32)
    return jax.lax.fori_loop(0, spec.nb, body, acc0) - lam * Wc


def _apply_delta(spec, R3, delta, *bargs):
    ops = _hoisted(spec, bargs)

    def body(p, R3):
        Zp = _chunk_Zp(spec, p * spec.block_rows, bargs, ops)
        with jax.named_scope("krr.delta_product"):
            upd = jax.lax.dot_general(
                Zp, delta.astype(Zp.dtype), (((1,), (0,)), ((), ())),
                precision=_prec(Zp.dtype),
                preferred_element_type=jnp.float32,
            )
            Rp = jax.lax.dynamic_index_in_dim(R3, p, 0, keepdims=False)
            return jax.lax.dynamic_update_index_in_dim(
                R3, Rp - upd, p, 0
            )

    return jax.lax.fori_loop(0, spec.nb, body, R3)


def _program(body, *spec):
    """``body`` as a ``jax.jit`` program under its name (device module
    ``jit_gram``, ``jit_zr``, ``jit_apply_delta``).  With no ``spec``
    the program is keyed by the static :class:`_ChunkSpec` it is called
    with first; with one it closes over it and takes the operands only,
    a program of its maker's that dies with it."""

    def program(*args):
        return body(*spec, *args)

    program.__name__ = body.__name__.lstrip("_")
    return jax.jit(program, static_argnums=() if spec else 0)


# The process's three chunk programs, ``gram(spec, lam, *bargs)``,
# ``zr(spec, lam, R, Wc, *bargs)`` and ``apply_delta(spec, R, delta,
# *bargs)``: built once a spec and operand shapes, then served from
# ``jax.jit``'s own cache.  λ is an f32 scalar operand, so a sweep over
# λ is one executable.
gram, zr, apply_delta = (_program(b) for b in (_gram, _zr, _apply_delta))


class _ChunkProgram:
    """A chunk program as its caller has it, whichever of the two kinds
    it is: launched (``profiling.launch``) and lowered with the operands
    alone, the static arguments it takes first, if any, already given."""

    def __init__(self, program, *static):
        self.program, self.static = program, static
        self.__name__ = program.__name__

    def __call__(self, *ops):
        return profiling.launch(self.program, *self.static, *ops)

    def lower(self, *ops):
        return self.program.lower(*self.static, *ops)


_CODE = (types.ModuleType, types.FunctionType, types.BuiltinFunctionType, type)


def _global_names(code):
    """The names ``code`` and the code nested in it may look up in the
    module's namespace (attribute names among them: more, never fewer)."""
    yield from code.co_names
    for const in code.co_consts:
        if isinstance(const, types.CodeType):
            yield from _global_names(const)


def _lives_with_its_module(fn, _seen=None) -> bool:
    """May a process-wide cache be keyed by ``fn``?  Yes for a plain
    function found under its qualified name in its module's namespace
    (``fn.__globals__``: the module need not be in ``sys.modules``) that
    has no closure and no default arguments, and reads nothing of that
    namespace but modules, classes and functions, those of its own
    namespace held to the same.  Such a function lives as long as the
    namespace, which it holds anyway, so the cache keeps nothing else
    alive; and a trace of it bakes in no array or number that the
    caller may rebind between calls (``X = ...`` at the top of a script
    or a notebook cell, read by ``block_fn`` and not passed to it)."""
    if (not isinstance(fn, types.FunctionType) or fn.__closure__
            or fn.__defaults__ or fn.__kwdefaults__):
        return False
    first, *rest = fn.__qualname__.split(".")
    found = fn.__globals__.get(first)
    for part in rest:
        found = getattr(found, part, None)
    if found is not fn:
        return False
    seen = set() if _seen is None else _seen
    seen.add(fn)
    for name in _global_names(fn.__code__):
        if name not in fn.__globals__:
            continue  # a builtin or an attribute
        read = fn.__globals__[name]
        if not isinstance(read, _CODE):
            return False
        if (isinstance(read, types.FunctionType)
                and read.__globals__ is fn.__globals__ and read not in seen
                and not _lives_with_its_module(read, seen)):
            return False
    return True


def streaming_krr_chunk_programs(
    maps, c, nb, block_rows, block_fn, feature_dtype
):
    """``(gram, zr, apply_delta)``, the three jitted programs of chunk
    ``c``'s sweep: ``gram(lam, *bargs)``, ``zr(lam, R, Wc, *bargs)``,
    ``apply_delta(R, delta, *bargs)``; λ is an f32 scalar operand.  What
    the trainer launches is what a test AOT-lowers (``.lower`` with the
    same operands), on a virtual mesh or for a described chip, to read
    the compiled HLO (``tests/test_collectives.py``,
    ``tests/test_tpu_compile.py``).

    **Built once a process.**  With a ``block_fn`` that lives with its
    module (:func:`_lives_with_its_module`) the programs are this
    module's ``gram``, ``zr`` and ``apply_delta``, keyed by a static
    spec: the maps *by value* (their JSON: kind, sizes, seed, counters),
    the chunk, ``nb``, ``block_rows``, ``feature_dtype`` and
    ``block_fn``'s *identity*.  A later call with a new kernel, context
    and map objects of equal value traces, lowers and compiles nothing;
    a new seed, size or dtype is three new executables, a new λ none.
    What ``jax.jit``'s cache then keeps alive is the first call's map
    objects with their memoized shift vectors (``sketch/rft.py::shifts``,
    ``::_turns``: s f32 numbers a map) and no array of the caller's.
    ``block_fn`` is traced once a spec and operand shapes: whatever it
    reads besides its arguments is read then and never again.

    Any other ``block_fn`` (a closure, a ``functools.partial``, a bound
    method, a lambda, a function with default arguments or one that
    reads data from its module's namespace) gets programs of its own
    that close over the spec and die with the call, as every call's did
    before: a cache keyed by such a function would keep alive whatever
    it closes over, and go on reading a global the caller has rebound.
    They are built again every call, a few hundred milliseconds of host
    work in which the device waits; pass data through ``block_args``
    instead.
    """
    spec = _ChunkSpec(
        maps if isinstance(maps, _Maps) else _Maps(maps), c, nb, block_rows,
        jnp.dtype(feature_dtype), block_fn,
    )
    if _lives_with_its_module(block_fn):
        return tuple(_ChunkProgram(p, spec) for p in (gram, zr, apply_delta))
    return tuple(
        _ChunkProgram(_program(b, spec)) for b in (_gram, _zr, _apply_delta))
