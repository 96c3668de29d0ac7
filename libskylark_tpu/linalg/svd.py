"""Randomized SVD (Halko-Martinsson-Tropp) — ≙ ``nla/svd.hpp``.

TPU design notes:

- The sketch ``Y = A·Omegaᵀ`` uses the counter-based JLT, so under GSPMD the
  test matrix is realized shard-locally and never communicated (invariant P5).
- Power iteration and QR re-orthonormalization are large tall-skinny
  matmuls/QRs: XLA maps the matmuls to the MXU and (for sharded A) inserts
  the reduce-scatter/all-gather schedule the reference hand-codes in
  Elemental (``sketch/dense_transform_Elemental_mc_mr.hpp:179,302,599``).
- The trailing small factorization (s×s / n×s) mirrors the reference's
  rank-replicated ``[*,*]`` matrices: it is computed replicated.
- Everything is jit-compatible: static shapes, ``lax.fori_loop`` for the
  iteration count.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .. import guard, telemetry
from ..core.context import SketchContext
from ..core.matrices import gaussian_matrix
from ..core.params import Params
from ..core.sparse import Prepared, edge_chunks, spmm
from ..parallel.mesh import fully_replicated
from ..resilient.chunked import ChunkedSolver
from ..sketch.base import Dimension
from ..sketch.dense import JLT
from ..utils import profiling

__all__ = [
    "SVDParams",
    "power_iteration",
    "approximate_svd",
    "approximate_svd_chunked",
    "approximate_symmetric_svd",
    "streaming_approximate_svd",
    "synthetic_lowrank_blocks",
    "gram_orth",
]


@dataclass
class SVDParams(Params):
    """≙ ``nla/svd.hpp:22-48`` (``approximate_svd_params_t``)."""

    oversampling_ratio: int = 2
    oversampling_additive: int = 0
    num_iterations: int = 0
    skip_qr: bool = False


def gram_orth(Y, passes: int = 2):
    """Orthonormalize the columns of tall-skinny ``Y`` via its Gram matrix.

    TPU-native replacement for the reference's distributed Householder QR /
    TSQR (``El::qr::ExplicitUnitary`` inside ``PowerIteration``,
    ``nla/svd.hpp:105-148``): per pass, ``G = YᵀY`` (one sharded matmul +
    psum), a replicated s×s ``eigh``, and ``Y ← Y·V·diag(lam^-1/2)``.  All
    heavy ops are MXU matmuls that GSPMD shards with Y; nothing tall is ever
    gathered (Householder QR would force a gather — JAX rejects sharded QR).
    Two passes give CholeskyQR2-grade orthogonality; the eigh (instead of
    Cholesky) keeps rank-deficient Y (sketches of exactly-low-rank A) from
    producing NaNs: clamped directions come out with tiny norm and are
    dropped by the rank-k truncation downstream.
    """
    with jax.named_scope("svd.gram_orth"):
        for _ in range(passes):
            # precision='highest' is load-bearing on BOTH products: the TPU
            # MXU default truncates f32 operands to bf16 mantissas, which
            # caps the achievable orthogonality at ~2e-3 no matter how many
            # passes run (caught by tests/_hw_guards.py).
            G = fully_replicated(jnp.dot(Y.T, Y, precision="highest"))
            lam, V = jnp.linalg.eigh(G)
            eps = jnp.asarray(jnp.finfo(Y.dtype).eps, G.dtype)
            floor = jnp.maximum(lam[-1], 0) * eps * G.shape[0]
            scale = jnp.where(
                lam > floor, jax.lax.rsqrt(jnp.maximum(lam, floor)), 0.0)
            Y = jnp.dot(Y, V * scale[None, :], precision="highest")
    return Y


_orth = gram_orth


def _sketch_size(k: int, params: SVDParams, n: int, m: int | None = None):
    """Validated (k, s): oversampled sketch width clamped to n
    (≙ ``nla/svd.hpp`` sizing, shared by all three SVD entry points)."""
    k = int(k)
    lim = n if m is None else min(m, n)
    if k > lim:
        raise ValueError(f"rank {k} exceeds min matrix dimension {lim}")
    s = min(k * params.oversampling_ratio + params.oversampling_additive, n)
    return k, max(s, k)


def _is_sparse(A) -> bool:
    """A BCOO, or one prepared for its products (``core.sparse.prepare``)."""
    return hasattr(A, "todense") or isinstance(A, Prepared)


def _times(A, X, *, transpose: bool = False):
    """``A·X`` (``Aᵀ·X``) inside the programs below: a prepared sparse
    operand goes through the chunked product (``core.sparse.spmm``); a
    dense one and a plain BCOO (``bcoo_dot_general``) are the parent's
    expression to the letter."""
    if isinstance(A, Prepared):
        return spmm(A, X, transpose=transpose)
    return A.T @ X if transpose else A @ X


@jax.jit
def _project(A, Q):
    """``Aᵀ·Q`` at full precision, A's rows contracted where they lie.  One
    program: an eager ``A.T`` is a transposed copy of A on every chip
    (5 GB a chip at 10⁷ × 512, which the operand's own 5 GB, the basis
    and U leave no room for)."""
    return jnp.dot(A.T, Q, precision="highest")


@partial(jax.jit, static_argnames=("orthogonalize",))
def _chunk(st, A, num_iters, niter, *, orthogonalize: bool):
    """The power-sweep segment: at most ``num_iters`` sweeps
    ``Y <- orth(A·(Aᵀ·Y))`` from ``st = {"it", "Y"}``, none past sweep
    ``niter``.

    Built once per (shapes, dtypes, dense or BCOO, ``orthogonalize``) and
    dispatched from ``jax.jit``'s own in-memory cache after that: the
    budget is two scalars, so one executable serves every chunk length and
    every ``num_iterations``.  A is an argument (dense array or BCOO
    pytree), a device buffer the program references and never a literal in
    it, and nothing that outlives the call holds A or Y."""
    stop = jnp.minimum(st["it"] + num_iters, niter)

    def cond(c):
        return c["it"] < stop

    def body(c):
        with jax.named_scope("svd.sweep_products"):
            Y = _times(A, _times(A, c["Y"], transpose=True))
        return dict(it=c["it"] + 1, Y=_orth(Y) if orthogonalize else Y)

    return lax.while_loop(cond, body, st)


def power_iteration(A, Q, num_iterations: int, orthogonalize: bool = True):
    """Subspace iteration ``Q <- orth((A·Aᵀ)·Q)``, repeated.

    ≙ ``PowerIteration`` (``nla/svd.hpp:71-149``): the reference's four
    orientation variants collapse to this one (pass ``A.T`` for the adjoint
    flavor).  ``orthogonalize`` toggles the per-step QR (``ortho`` flag).
    """
    if num_iterations <= 0:
        return Q

    def body(_, Q):
        Q = A @ (A.T @ Q)
        return _orth(Q) if orthogonalize else Q

    return lax.fori_loop(0, num_iterations, body, Q)


def approximate_svd_chunked(
    A,
    rank: int,
    context: SketchContext,
    params: SVDParams | None = None,
) -> ChunkedSolver:
    """Chunkable randomized SVD: the power-iteration sweeps (the long part
    for ``num_iterations > 0``) run as jitted ≤ k-step segments whose state
    (iteration counter + current basis Y) checkpoints between chunks; the
    sketch in ``init_state`` is counter-based (JLT), so a resumed process
    rebuilds the identical test matrix and the resumed run is bit-identical
    to the uninterrupted chunked run.  ``extract_result`` performs the
    trailing QR → small SVD → truncate of :func:`approximate_svd`.
    """
    params = params or SVDParams()
    if not hasattr(A, "todense"):  # keep BCOO sparse inputs as-is
        A = jnp.asarray(A)
    m, n = A.shape
    k, s = _sketch_size(rank, params, n, m)
    niter = max(params.num_iterations, 0)
    orthogonalize = not params.skip_qr

    def init_state():
        # Q = A·Omegaᵀ — rowwise JLT sketch (nla/svd.hpp:255-257).
        with telemetry.span("svd.sketch"):
            omega = JLT(n, s, context)
            return dict(
                it=jnp.zeros((), jnp.int32),
                Y=omega.apply(A, Dimension.ROWWISE),
            )

    def step_chunk(st, num_iters: int):
        # the first call at a shape: trace, lower, cache key, fetch; every
        # later one: a dispatch from jit's cache
        with telemetry.span("svd.power"):
            return profiling.launch(
                _chunk, st, A, num_iters, niter, orthogonalize=orthogonalize)

    def extract_result(st):
        Y = st["Y"]
        with telemetry.span("svd.project"):
            # The power-iteration body already ends orthonormalized unless
            # skip_qr, so only orthonormalize here when the loop didn't.
            Q = Y if (niter > 0 and orthogonalize) else _orth(Y)

            # B = Aᵀ·Q (n, s); small SVD; rotate back (nla/svd.hpp:266-285).
            # Both products pinned: the MXU default would put ~2e-3 (bf16)
            # error into the singular values (via B) and U's orthogonality
            # (via the rotation) on hardware.  The power-iteration sweeps
            # keep the fast default — they only steer the subspace.
            # (BCOO has no precision knob and does not ride the MXU bf16
            # path — its matmul keeps the sparse dispatch.)
            AtQ = (A.T @ Q if hasattr(A, "todense")
                   else profiling.launch(_project, A, Q))
            B = fully_replicated(AtQ)
        with telemetry.span("svd.small"):
            W, sv, Zt = jnp.linalg.svd(B, full_matrices=False)  # B = W·sv·Zt
        with telemetry.span("svd.rotate"):
            # A ≈ Q·Bᵀ = (Q·Ztᵀ)·diag(sv)·Wᵀ
            U = jnp.dot(Q, Zt.T, precision="highest")
            return U[:, :k], sv[:k], W[:, :k]

    return ChunkedSolver(
        init_state=init_state,
        step_chunk=step_chunk,
        extract_result=extract_result,
        is_done=lambda st: int(st["it"]) >= niter,
        iteration=lambda st: int(st["it"]),
        kind="approximate_svd",
    )


def approximate_svd(
    A,
    rank: int,
    context: SketchContext,
    params: SVDParams | None = None,
    *,
    return_info: bool = False,
):
    """Randomized truncated SVD: returns ``(U, s, V)`` with
    ``A ≈ U @ diag(s) @ V.T``, U: (m, rank), V: (n, rank).

    ≙ ``ApproximateSVD`` (``nla/svd.hpp:222-318``): JLT sketch of the row
    space → power iteration → QR → small SVD → truncate.  One chunk of the
    full sweep budget through :func:`approximate_svd_chunked`.

    Guarding (``SKYLARK_GUARD``, on by default): the factors are certified
    posteriorly (``guard.certify_svd`` — finiteness + one-matvec residual
    check on the leading triplet); a failed certificate climbs the ladder
    (fresh-seed resketch → grown oversampling → dense ``jnp.linalg.svd``
    fallback).  Attempt 0 reuses the caller's context, so healthy runs are
    bit-identical to the unguarded path.  ``return_info=True`` returns
    ``((U, s, V), info)`` with the attempts in ``info["recovery"]`` and
    their count in ``info["attempts"]`` (1 on a sound run).
    """
    with telemetry.span("randomized_svd"):
        out, report = _guarded_svd(A, rank, context, params or SVDParams())
    if return_info:
        # attempts: the factorizations this call ran (1 on a sound run)
        return out, {
            "attempts": max(len(report.attempts), 1),
            "recovery": report.to_dict(),
        }
    return out


def _guarded_svd(A, rank, context, params):
    """``((U, s, V), report)``: one factorization, then up the guard's
    ladder while its certificate fails."""

    def run(ctx, p):
        sol = approximate_svd_chunked(A, rank, ctx, p)
        st = sol.step_chunk(sol.init_state(), max(p.num_iterations, 1))
        return sol.extract_result(st)

    # Under an enclosing jit trace the host-side certificate reads and
    # ladder control flow cannot run — emit the plain unguarded graph.
    if not guard.enabled() or guard.is_traced(A):
        return run(context, params), guard.RecoveryReport.disabled(
            "randomized_svd"
        )

    m, n = A.shape
    report = guard.RecoveryReport(stage="randomized_svd")
    retries = guard.max_retries()
    for i in range(retries + 1):
        if i == 0:
            action, ctx, p = "initial", context, params
        elif i == 1:
            action, ctx, p = "resketch", guard.derived_context(context, i), params
        else:
            # Grow the sketch width geometrically through the additive
            # oversampling term (clamped to n by _sketch_size).
            action, ctx = "grow", guard.derived_context(context, i)
            p = replace(
                params,
                oversampling_additive=params.oversampling_additive
                + rank * (2 ** (i - 1)),
            )
        U, sv, V = run(ctx, p)
        # the certificate's three host reads: the host waits for U here
        with telemetry.span("guard.certify"):
            cert = guard.certify_svd(A, U, sv, V)
        _, width = _sketch_size(rank, p, n, m)
        report.record(
            action, verdict=cert.verdict, detail=cert.detail,
            sketch_size=width,
        )
        if cert.ok:
            report.recovered = i > 0
            return (U, sv, V), report
    Ad = A.todense() if hasattr(A, "todense") else A
    Uf, svf, Vtf = jnp.linalg.svd(jnp.asarray(Ad), full_matrices=False)
    report.record(
        "fallback", verdict=guard.FALLBACK, detail="dense jnp.linalg.svd"
    )
    report.recovered = True
    return (Uf[:, :rank], svf[:rank], Vtf[:rank].T), report


@jax.jit
def _sym_sketch(A, Wt):
    """``A·Ωᵀ`` with the realized ``Ωᵀ`` (n × s) an argument: the product
    of ``JLT.apply(A, ROWWISE)`` as one program that no sketch's seed is
    part of."""
    return _times(A, Wt)


@partial(jax.jit, static_argnames=("k", "orthogonalize"))
def _ritz(A, Y, *, k: int, orthogonalize: bool):
    """Rayleigh-Ritz on the span of ``Y`` (≙ ``nla/svd.hpp:360-380``):
    ``T = Qᵀ·A·Q`` symmetrized, its eigenpairs sorted by |λ|, the first
    ``k``.  Pinned at highest: T's error lands directly in the
    eigenvalues and in V's orthogonality (a BCOO product has no
    precision to pin: it is f32 sums)."""
    Q = _orth(Y) if orthogonalize else Y
    AQ = _times(A, Q) if _is_sparse(A) else jnp.dot(
        A, Q, precision="highest"
    )
    # Qᵀ stands as an array before the product, as it did op by op: folded
    # into the dot, XLA:CPU sums T in another order and the dense result
    # is no longer the eager recurrence's to the bit
    Qt = lax.optimization_barrier(Q.T)
    T = fully_replicated(jnp.dot(Qt, AQ, precision="highest"))
    T = (T + T.T) / 2
    lam, W = jnp.linalg.eigh(T)
    order = jnp.argsort(-jnp.abs(lam))
    lam = lam[order][:k]
    V = jnp.dot(Q, W, precision="highest")[:, order[:k]]
    return V, lam


def approximate_symmetric_svd(
    A,
    rank: int,
    context: SketchContext,
    params: SVDParams | None = None,
    *,
    return_info: bool = False,
    stage: str = "symsvd",
):
    """Randomized eigendecomposition of symmetric A: ``(V, lam)`` with
    ``A ≈ V @ diag(lam) @ V.T`` (eigenvalues sorted by |lam| descending).

    ≙ ``ApproximateSymmetricSVD`` (``nla/svd.hpp:321-392``): explicit
    Gaussian test matrix, subspace iteration, Schur-Rayleigh-Ritz step
    (the reference's ``HermitianEig`` on the compressed ``QᵀAQ``).

    Three cached programs, each under a span ``<stage>.sketch``,
    ``<stage>.power``, ``<stage>.ritz`` (an entry that has stages of its
    own, ``approximate_ase``, gives its own name): ``_sym_sketch``, the
    sweep segment ``_chunk`` of :func:`approximate_svd` with the whole
    budget, ``_ritz``.  A (dense, a BCOO, or one prepared by
    ``core.sparse.prepare``, whose products are ``core.sparse.spmm``:
    the route for a graph too large for ``bcoo_dot_general``) and the
    panel are arguments of all three, so a warm call traces and lowers
    nothing.  ``return_info=True`` returns
    ``((V, lam), info)``: ``products`` (products with A the call ran:
    the sketch's, two a sweep, the Ritz step's), ``iterations``, ``nnz``
    and ``edge_chunks`` (steps a product walks a prepared operand's
    nonzeros in; the entries of a dense A, and 0, as for a plain BCOO);
    for a prepared operand with a hot table also ``tables`` (the tables
    its nonzeros gather from, the hot one among them) and ``hot_share``
    (the share of the nonzeros that gather from the hot one).  Any other
    operand's ``info`` is the four keys it was: ``A.tables`` and
    ``A.hot_share`` (0.0) of a prepared operand say the same without.
    """
    params = params or SVDParams()
    sparse = _is_sparse(A)
    if not sparse:
        A = jnp.asarray(A)
    n = A.shape[0]
    k, s = _sketch_size(rank, params, n)
    niter = max(params.num_iterations, 0)
    orthogonalize = not params.skip_qr
    dtype = A.dtype if jnp.issubdtype(A.dtype, jnp.floating) else jnp.float32

    with telemetry.span(f"{stage}.sketch"):
        # A·Omegaᵀ (symmetric A): Omega realized as JLT.apply realizes it
        Wt = JLT(n, s, context).realize(dtype).T
        Y = profiling.launch(_sym_sketch, A, Wt)
    with telemetry.span(f"{stage}.power"):
        if niter:
            st = dict(it=jnp.zeros((), jnp.int32), Y=Y)
            Y = profiling.launch(
                _chunk, st, A, niter, niter, orthogonalize=orthogonalize
            )["Y"]
    with telemetry.span(f"{stage}.ritz"):
        out = profiling.launch(
            _ritz, A, Y, k=k, orthogonalize=not (niter and orthogonalize)
        )
    if return_info:
        info = {
            "products": 2 + 2 * niter,
            "iterations": niter,
            "nnz": A.nse if sparse else A.size,
            "edge_chunks": edge_chunks(A, s) if isinstance(A, Prepared) else 0,
        }
        if isinstance(A, Prepared) and A.hot_nse:
            info.update(tables=A.tables, hot_share=A.hot_share)
        return out, info
    return out


# ---------------------------------------------------------------------------
# Streaming (matrix-free) randomized SVD — the n=1e7-row regime.
#
# ≙ the scale `skylark_svd --profile` exists for (nla/skylark_svd.cpp:37-60):
# A too large for one memory, processed in row panels.  The reference's
# answer is Elemental's distributed storage; on a single TPU chip the
# counter-RNG design gives a better one — row blocks are *regenerated* (or
# re-streamed) per sweep inside one compiled program, so only one (B, n)
# block plus (n, s)/(s, s) accumulators are ever resident.  This is the
# same memory-bounded pattern as ml's large_scale_kernel_ridge.


def streaming_approximate_svd(
    block_fn,
    shape: tuple[int, int],
    rank: int,
    context: SketchContext,
    params: SVDParams | None = None,
    block_rows: int = 65536,
    materialize_u: bool = False,
    mesh=None,
):
    """Randomized truncated SVD of a row-streamed A (m, n).

    With ``mesh`` (a ``jax.sharding.Mesh`` with Auto axes), each panel is
    sharded over the mesh's row axis (≙ the ``[VC,*]`` long-dimension
    distribution, P2): panel generation and the panel matmuls run
    distributed, and GSPMD inserts the psum for the small replicated
    accumulators — the streamed schedule composes with multi-chip without
    code changes in ``block_fn``.  Explicit-axes meshes are rejected (the
    accumulator contractions would each need an ``out_sharding``).

    ``block_fn(start_row, rows)`` returns the (rows, n) panel of A; it must
    be jit-traceable with a traced ``start_row`` (counter-generated
    matrices and sharded arrays qualify; see
    :func:`synthetic_lowrank_blocks`), and must return *bit-identical*
    panels every time it is called — it is re-traced into more than one
    compiled program, and the whitening step amplifies any cross-program
    drift by 1/σ_min (avoid default-precision matmuls inside it).  Each
    sweep re-requests every panel — O(q+2) passes over A, O(B·n + n·s)
    resident memory.

    Returns ``(u_block, s, V)`` where ``u_block(i)`` yields rows
    ``[i·B, (i+1)·B)`` of U (the factored form keeps U off-memory for huge
    m); with ``materialize_u=True`` the first element is U itself (m, k).

    Math ≙ ``ApproximateSVD`` with explicit Gaussian test matrix: sweeps of
    ``W ← Aᵀ(A·Ω)`` with Gram orthonormalization (power iteration), then a
    fused pass accumulating ``G = YᵀY`` and ``M = YᵀA`` (Y = A·Ω), a second
    streamed whitening pass (CholeskyQR2), and a small SVD of ``B = QᵀA``.

    f32 note: with ``num_iterations=0`` on a noisy spectrum the Gram
    whitening's f32 error mixes signal into the oversampling directions
    and the rank-k truncation can lose real signal (measured ~0.3 relative
    sv error on hardware); for that reason the streaming path defaults to
    ``num_iterations=1`` when ``params`` is omitted (pass explicit params
    to override).
    """
    params = params or SVDParams(num_iterations=1)
    if mesh is not None and any(
        t == jax.sharding.AxisType.Explicit
        for t in mesh.axis_types
    ):
        raise ValueError(
            "streaming_approximate_svd needs an Auto-axes mesh "
            "(make_mesh(..., explicit=False)); explicit typed-sharding "
            "would require out_sharding on every accumulator contraction"
        )
    m, n = shape
    k, s = _sketch_size(rank, params, n, m)
    if block_rows <= 0:
        raise ValueError(f"block_rows must be positive, got {block_rows}")
    if m % block_rows:
        raise ValueError(f"m={m} not divisible by block_rows={block_rows}")
    nblocks = m // block_rows

    # Accumulator dtype follows the panels (f64 panels → f64 accumulators
    # and eps — the x64 parity path must not silently demote to f32).
    panel_dtype = jax.eval_shape(
        lambda s0: block_fn(s0, block_rows),
        jax.ShapeDtypeStruct((), jnp.int32),
    ).dtype
    acc = jnp.promote_types(panel_dtype, jnp.float32)

    Om = gaussian_matrix(context, (n, s), dtype=acc)

    def _shard_panel(Ab):
        """Row-shard a panel over the mesh (no-op without a mesh)."""
        if mesh is None:
            return Ab
        from ..parallel.mesh import constrain_rows

        return constrain_rows(Ab, mesh)

    def _panel_y(Ab, Om):
        """Y panel = A_b·Ω at full f32 precision.  'highest' is load-
        bearing: the whitener amplifies Y errors by 1/σ_min(kept), and Y
        must be numerically IDENTICAL between the factor program and
        ``u_block``'s separately-compiled program — default-precision
        (bf16-pass) matmuls can differ across compilations, which showed
        up as O(1) orthogonality loss in U on real hardware."""
        return jnp.dot(Ab, Om.astype(Ab.dtype), precision="highest")

    def _sweep(Om):
        """One power pass: Aᵀ(A·Ω) accumulated over row panels.  Default
        matmul precision — the sweep only steers the subspace (any Ω
        works); the resulting Omq is computed once and reused as an array,
        so the cross-program consistency that forces ``_panel_y`` to
        'highest' elsewhere does not apply here."""

        def body(i, W):
            Ab = _shard_panel(block_fn(i * block_rows, block_rows))
            return W + jnp.dot(
                Ab.T, Ab @ Om.astype(Ab.dtype),
                preferred_element_type=acc,
            )

        return lax.fori_loop(0, nblocks, body, jnp.zeros((n, s), acc))

    @jax.jit
    def _power_and_factor():
        W = Om
        for _ in range(max(params.num_iterations, 0)):
            # skip_qr ≙ the reference's ortho flag: raw power sweeps
            # (overflow-prone for spread spectra — the user's choice).
            W = _sweep(W) if params.skip_qr else _orth(_sweep(W))
        Omq = W if params.num_iterations > 0 else Om

        def body(i, carry):
            G, M = carry
            Ab = _shard_panel(block_fn(i * block_rows, block_rows))
            Yb = _panel_y(Ab, Omq)
            G = G + jnp.dot(
                Yb.T, Yb, precision="highest",
                preferred_element_type=acc,
            )
            M = M + jnp.dot(
                Yb.T, Ab, precision="highest",
                preferred_element_type=acc,
            )
            return G, M

        G, M = lax.fori_loop(
            0,
            nblocks,
            body,
            (jnp.zeros((s, s), acc), jnp.zeros((s, n), acc)),
        )
        # Whiten: Q = (Y·T1)·T2, both factors eigh-based V·lam^{-1/2}.
        def whiten(G, rel_floor):
            lam, V = jnp.linalg.eigh(G)
            floor = jnp.maximum(lam[-1], 0) * rel_floor
            scale = jnp.where(
                lam > floor, jax.lax.rsqrt(jnp.maximum(lam, floor)), 0.0
            )
            return V * scale[None, :]

        # Stage 1: loose floor (4·eps) — keep marginal directions whose
        # Gram eigenvalues are only a few× the f32 representation noise;
        # stage 2 either repairs or rejects them.
        eps = jnp.finfo(acc).eps
        T1 = whiten(G, 4.0 * eps)  # (s, s)
        # Stage 2 (streamed CholeskyQR2): one-pass Gram whitening leaves
        # ~eps·cond(G) orthogonality error — O(1) in f32 when Y mixes
        # signal and noise-level directions.  Re-accumulate the Gram of
        # the *whitened* panels: genuine directions land near 1 and are
        # re-whitened exactly; directions whose stage-1 estimate was pure
        # representation noise land far below 1 and are dropped (0.25
        # reliability floor).  Exactly-rank-deficient A never reaches
        # stage 2 (true zero eigenvalues are below even the loose floor).
        def body2(i, G2):
            Ab = _shard_panel(block_fn(i * block_rows, block_rows))
            Qb = jnp.dot(
                _panel_y(Ab, Omq), T1.astype(Ab.dtype), precision="highest"
            )
            return G2 + jnp.dot(
                Qb.T, Qb, precision="highest",
                preferred_element_type=acc,
            )

        G2 = lax.fori_loop(0, nblocks, body2, jnp.zeros((s, s), acc))
        T2 = whiten(G2, 0.25)
        # CRITICAL: T1 and T2 stay FACTORED.  T1's columns span orders of
        # magnitude; forming T1·T2 mixes those scales before the O(1)
        # whitening of Y·T1 happens, and the associativity error destroys
        # Q's orthonormality.  Apply left-to-right: ((Y·T1)·T2)·Ub.
        # precision='highest' on the small factor products too: a
        # default-precision (bf16-mantissa) rot2 alone puts ~4e-3 of
        # non-orthogonality into U on hardware (round-3 hw guard).
        B = jnp.dot(
            T2.T, jnp.dot(T1.T, M, precision="highest"), precision="highest"
        )  # = Qᵀ·A  (s, n)
        Ub, sv, Vt = jnp.linalg.svd(B, full_matrices=False)
        rot2 = jnp.dot(T2, Ub[:, :k], precision="highest")  # (Y·T1)·rot2 = U
        return Omq, T1, rot2, sv[:k], Vt[:k].T

    Omq, T1, rot2, sv, V = _power_and_factor()

    @jax.jit
    def u_block_traced(start):
        Ab = _shard_panel(block_fn(start, block_rows))
        Q1 = jnp.dot(_panel_y(Ab, Omq), T1.astype(Ab.dtype), precision="highest")
        return jnp.dot(Q1, rot2.astype(Ab.dtype), precision="highest")

    def u_block(i: int):
        """Rows [i·block_rows, (i+1)·block_rows) of U."""
        return u_block_traced(i * block_rows)

    if materialize_u:
        U = jnp.concatenate([u_block(i) for i in range(nblocks)], axis=0)
        return U, sv, V
    return u_block, sv, V


def synthetic_lowrank_blocks(
    context: SketchContext,
    m: int,
    n: int,
    r: int,
    noise: float = 0.0,
    dtype=jnp.float32,
    decay: float = 1.0,
):
    """Jit-traceable row-panel generator for A = L·diag(w)·Rᵀ + noise·E,
    with L (m, r), R (n, r), E (m, n) counter-generated (any panel is a
    window of the logical stream — ``core/random.py::sample_window``) and
    ``w[j] = decay^j``.  ≙ the synthetic ``--profile`` matrix of
    ``nla/skylark_svd.cpp:37-60``, but never materialized.
    """
    from ..core.random import sample_window

    base_L = context.reserve(m * r)
    base_E = context.reserve(m * n)
    R = gaussian_matrix(context, (n, r), dtype=dtype)
    wdtype = jnp.promote_types(dtype, jnp.float32)
    w = jnp.asarray(decay, wdtype) ** jnp.arange(r)
    Rw = (R * w[None, :].astype(dtype)).T  # (r, n)

    def block_fn(start_row, rows: int):
        Lb = sample_window(
            "normal", context.seed, base_L, (m, r),
            offset=(start_row, 0), shape=(rows, r), dtype=dtype,
        )
        # highest: panels must be BIT-IDENTICAL across separately compiled
        # programs (streaming_approximate_svd's contract) — a default-
        # precision matmul can fuse differently per program and break it.
        Ab = jnp.dot(Lb, Rw, precision="highest")
        if noise:
            Eb = sample_window(
                "normal", context.seed, base_E, (m, n),
                offset=(start_row, 0), shape=(rows, n), dtype=dtype,
            )
            Ab = Ab + jnp.asarray(noise, dtype) * Eb
        return Ab

    return block_fn
