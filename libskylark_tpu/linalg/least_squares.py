"""Least-squares solvers: exact l2 paths + sketch-and-solve.

- ``exact_least_squares`` ≙ the ``regression_solver_t`` l2 specializations
  (``algorithms/regression/linearl2_regression_solver_Elemental.hpp:23-631``)
  with the tag dispatch (``qr/sne/ne/svd_l2_solver_tag``) as a string arg.
- ``approximate_least_squares`` ≙ sketch-and-solve
  (``nla/least_squares.hpp:42-184`` + ``sketched_regression_solver_Elemental
  .hpp:29-104``): sketch A and B columnwise once, exact-solve the small
  problem.  Like the reference, defaults to FJLT (sketch size 4·width) for
  dense inputs; sparse (BCOO) inputs auto-select CWT (input-sparsity time).

TPU notes: QR/Cholesky of the (sketched) s×n problem is replicated-small
(≙ the reference's ``[*,*]`` matrices); the sketch itself is the sharded
MXU-heavy op.  All functions are jit-compatible.
"""

from __future__ import annotations

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
from jax.scipy.linalg import cho_factor, cho_solve, solve_triangular

from .. import guard, plans, telemetry
from ..core.context import SketchContext
from ..core.params import Params
from ..sketch.base import Dimension, create_sketch

__all__ = [
    "LeastSquaresParams",
    "exact_least_squares",
    "approximate_least_squares",
    "streaming_least_squares",
]


@dataclass
class LeastSquaresParams(Params):
    """≙ ``nla/least_squares.hpp`` params: sketch choice + size."""

    sketch_type: str | None = None  # None → FJLT dense / CWT sparse
    sketch_size: int | None = None  # default 4 * width (least_squares.hpp:60)


def _svd_lstsq(A, B):
    """Pseudoinverse path shared by ``alg="svd"`` and the guarded ``ne``
    fallback (rank-deficiency-proof)."""
    U, s, Vt = jnp.linalg.svd(A, full_matrices=False)
    cutoff = jnp.finfo(A.dtype).eps * max(A.shape) * s[0]
    sinv = jnp.where(s > cutoff, 1.0 / s, 0.0)
    return Vt.T @ (sinv[:, None] * (U.T @ B))


def exact_least_squares(A, B, alg: str = "qr"):
    """Solve ``min_X ||A X - B||_F`` for tall A; returns X (n, k).

    ``alg`` ∈ {"qr", "sne", "ne", "svd"} ≙ the reference's
    ``qr/sne/ne/svd_l2_solver_tag`` solver tags.

    ``ne`` note: ``cho_factor`` on a singular/indefinite Gram matrix
    returns NaNs WITHOUT error.  Under the guard layer (default) a
    non-finite factor reroutes to the ``svd`` pseudoinverse path (inside
    jit: a ``lax.cond`` branch, so the function stays jit-compatible);
    with ``SKYLARK_GUARD=0`` the eager path raises
    ``NumericalHealthError`` instead of returning silent NaNs.
    """
    A = jnp.asarray(A)
    B = jnp.asarray(B)
    squeeze = B.ndim == 1
    if squeeze:
        B = B[:, None]
    if alg == "qr":
        # Householder QR; X = R⁻¹ Qᵀ B (≙ El::qr::ApplyQ path).
        Q, R = jnp.linalg.qr(A, mode="reduced")
        X = solve_triangular(R, Q.T @ B, lower=False)
    elif alg == "sne":
        # Semi-normal equations: R from QR(A), solve RᵀR X = Aᵀ B
        # (≙ El::qr::ExplicitTS + two triangular solves).
        R = jnp.linalg.qr(A, mode="r")
        Y = solve_triangular(R.T, A.T @ B, lower=True)
        X = solve_triangular(R, Y, lower=False)
    elif alg == "ne":
        # Normal equations via Cholesky (≙ ne_l2_solver_tag).
        G = A.T @ A
        c, low = cho_factor(G)
        AtB = A.T @ B
        finite = jnp.all(jnp.isfinite(c))
        guarded = guard.enabled()
        if isinstance(finite, jax.core.Tracer):
            if guarded:
                X = jax.lax.cond(
                    finite,
                    lambda: cho_solve((c, low), AtB),
                    lambda: _svd_lstsq(A, B),
                )
            else:
                X = cho_solve((c, low), AtB)
        elif bool(finite):
            X = cho_solve((c, low), AtB)
        elif guarded:
            X = _svd_lstsq(A, B)
        else:
            from ..utils.exceptions import NumericalHealthError

            raise NumericalHealthError(
                "cho_factor returned non-finite factors (singular or "
                "indefinite Gram matrix) in exact_least_squares(alg='ne')",
                stage="exact_ls_ne",
            )
    elif alg == "svd":
        # Pseudoinverse through the SVD (≙ svd_l2_solver_tag).
        X = _svd_lstsq(A, B)
    else:
        raise ValueError(f"unknown exact LS alg {alg!r}")
    return X[:, 0] if squeeze else X


def approximate_least_squares(
    A,
    B,
    context: SketchContext,
    params: LeastSquaresParams | None = None,
    alg: str = "qr",
    *,
    route: str | None = None,
    fault_plan=None,
    return_info: bool = False,
):
    """Sketch-and-solve LS: sketch the rows of (A, B), solve exactly.

    ≙ ``ApproximateLeastSquares`` (``nla/least_squares.hpp:42-184``):
    construct S once (columnwise, size s×m), apply to A at build and to B at
    solve (``sketched_regression_solver_Elemental.hpp:60-104``).

    Guarding (``SKYLARK_GUARD``, on by default): each sketch is certified
    (``guard.certify_sketch`` — finiteness + ``cond_est``) and a bad draw
    climbs the recovery ladder (fresh-seed resketch → grow sketch size →
    exact dense ``svd`` solve).  Attempt 0 reuses the caller's context and
    sketch order, so a healthy run returns bit-identical results to the
    unguarded path.  ``fault_plan`` exposes the ladder's injection point
    (``FaultPlan.corrupt_sketch`` — ``nan_at``/``bad_sketch_at`` keyed by
    attempt index).  With ``return_info=True`` returns ``(x, info)`` where
    ``info["recovery"]`` is the :class:`~libskylark_tpu.guard.
    RecoveryReport` dict (``guarded=False`` under ``SKYLARK_GUARD=0``).

    Routing (``SKYLARK_POLICY``, on by default): the call consults
    :func:`~libskylark_tpu.policy.choose_route` with the problem's
    signature.  With no matured profile entry the decision is exactly the
    defaults above (bit-parity contract, ``tests/test_policy.py``); a
    matured entry may reroute to ``blendenpik``/``lsrn``/``refine``/
    ``exact``, shrink the sketch dimension toward the smallest
    certified-OK size, or sketch bf16-first (escalating back to the
    input dtype when attempt 0's certificate is not OK).  ``route`` pins
    the route explicitly (one of ``"sketch"``, ``"refine"``,
    ``"blendenpik"``, ``"lsrn"``, ``"exact"``); pinned ``params`` fields
    always win.  ``info["policy"]`` carries the decision.
    """
    from .. import policy
    from ..policy.decide import LS_ROUTES

    with telemetry.span("sketch_solve"):
        if route is not None and route not in LS_ROUTES:
            raise ValueError(
                f"unknown least-squares route {route!r}; one of {LS_ROUTES}"
            )
        params = params or LeastSquaresParams()
        is_sparse = hasattr(A, "todense")
        if not is_sparse:
            A = jnp.asarray(A)
        B = jnp.asarray(B)
        squeeze = B.ndim == 1
        if squeeze:
            B = B[:, None]
        m, n = A.shape
        guard_on = guard.enabled() and not guard.is_traced(A, B)
        decision = policy.consult(
            "ls",
            m=m,
            n=n,
            targets=B.shape[1],
            dtype=(A.data.dtype.name if is_sparse else A.dtype.name),
            sparse=is_sparse,
            route=route,
            sketch_type=params.sketch_type,
            sketch_size=params.sketch_size,
            guard_on=guard_on,
        )
        s = decision.sketch_size
        stype = decision.sketch_type
        default_size = min(4 * n, m)

        # -- profile-learned reroutes (never taken on an empty store) ------------
        if decision.route == "exact":
            A_dense = A.todense() if is_sparse else A
            X = exact_least_squares(A_dense, B, alg="svd")
            report = (
                guard.RecoveryReport(stage="sketch_and_solve_ls")
                if guard_on
                else guard.RecoveryReport.disabled("sketch_and_solve_ls")
            )
            if guard_on:
                guard.check_finite(X, "exact_ls", report=report)
            out = X[:, 0] if squeeze else X
            info = {"recovery": report.to_dict(), "policy": decision.to_dict()}
            policy.observe(decision, info, default_size=default_size)
            telemetry.run_summary("sketch_and_solve_ls", info)
            return (out, info) if return_info else out
        if decision.route in ("blendenpik", "lsrn"):
            from ..solvers.accelerated import (
                FasterLeastSquaresParams,
                faster_least_squares,
                lsrn_least_squares,
            )

            fls = FasterLeastSquaresParams(sketch_type=params.sketch_type)
            solver = (
                faster_least_squares
                if decision.route == "blendenpik"
                else lsrn_least_squares
            )
            X, rinfo = solver(A, B, context, fls)
            out = X[:, 0] if squeeze else X
            info = dict(rinfo)
            info["policy"] = decision.to_dict()
            policy.observe(decision, info, default_size=default_size)
            telemetry.run_summary("sketch_and_solve_ls", info)
            return (out, info) if return_info else out
        if decision.route == "refine":
            from ..solvers.refine import RefineParams, refine_least_squares

            rp = RefineParams(
                sketch_type=decision.sketch_type,
                sketch_size=decision.sketch_size,
            )
            X, rinfo = refine_least_squares(
                A, B, context, rp, fault_plan=fault_plan
            )
            out = X[:, 0] if squeeze else X
            info = dict(rinfo)
            info["policy"] = decision.to_dict()
            policy.observe(
                decision, info, default_size=default_size,
                refine=rinfo.get("refine"),
            )
            telemetry.run_summary("sketch_and_solve_ls", info)
            return (out, info) if return_info else out

        # Under an enclosing jit trace the host-side certificate reads and
        # ladder control flow cannot run — emit the plain unguarded graph.
        if not guard_on:
            S = create_sketch(stype, m, s, context)
            # Plan-cached applies: repeated sketch-and-solve calls at the same
            # shape (parameter sweeps, restarts) reuse one fused executable.
            SA = plans.apply(S, A, Dimension.COLUMNWISE)
            SB = plans.apply(S, B, Dimension.COLUMNWISE)
            if fault_plan is not None:
                SA = fault_plan.corrupt_sketch(0, SA)
            with telemetry.span("sketch_solve.small"):
                X = exact_least_squares(SA, SB, alg=alg)
            out = X[:, 0] if squeeze else X
            if return_info:
                report = guard.RecoveryReport.disabled("sketch_and_solve_ls")
                info = {
                    "recovery": report.to_dict(),
                    "policy": decision.to_dict(),
                }
                telemetry.run_summary("sketch_and_solve_ls", info)
                return out, info
            return out

        def run_guarded(A_in, cast_solve):
            """One trip up the guard ladder; ``cast_solve`` lifts the (narrow)
            sketch output back to B's dtype before certification + solve (the
            small s×n problem always solves at full precision)."""

            def attempt(ctx, s_i, i):
                S = create_sketch(stype, m, s_i, ctx)
                SA = plans.apply(S, A_in, Dimension.COLUMNWISE)
                SB = plans.apply(S, B, Dimension.COLUMNWISE)
                if cast_solve:
                    SA = SA.astype(B.dtype)
                if fault_plan is not None:
                    SA = fault_plan.corrupt_sketch(i, SA)
                # the certificate and its cond_est: host waits on the sketch
                with telemetry.span("guard.certify"):
                    cert = guard.certify_sketch(
                        SA, stage="sketch_and_solve_ls"
                    )
                if not cert.ok:
                    return None, cert
                with telemetry.span("sketch_solve.small"):
                    X = exact_least_squares(SA, SB, alg=alg)
                with telemetry.span("guard.check"):  # waits for the solve
                    finite = guard.tree_all_finite(X)
                if not finite:
                    cert = replace(
                        cert,
                        verdict=guard.RESKETCH,
                        detail="non-finite small-problem solution",
                    )
                    return None, cert
                return X, cert

            def fallback():
                A_dense = A.todense() if is_sparse else A
                return exact_least_squares(A_dense, B, alg="svd")

            return guard.run_ladder(
                "sketch_and_solve_ls", context, s, m, attempt, fallback
            )

        def _ok0(report):
            attempts = report.to_dict().get("attempts") or []
            return bool(attempts) and attempts[0].get("verdict") == guard.OK

        bf16_note = None
        if decision.compute_dtype == "bfloat16":
            # bf16-first: the MXU-heavy sketch runs at bf16 (the
            # f32-accumulable kernel entry points make it nearly free); the
            # guard certificate checks the lifted sketch and a non-OK attempt
            # 0 escalates the whole solve back to the input dtype.
            X, report = run_guarded(A.astype(jnp.bfloat16), True)
            if not _ok0(report):
                decision.escalated = True
                bf16_note = "fail"
                X, report = run_guarded(A, False)
        else:
            X, report = run_guarded(A, False)
        out = X[:, 0] if squeeze else X
        info = {"recovery": report.to_dict(), "policy": decision.to_dict()}
        policy.observe(
            decision, info, default_size=default_size, bf16=bf16_note
        )
        telemetry.run_summary("sketch_and_solve_ls", info)
        if return_info:
            return out, info
        return out


def streaming_least_squares(
    source,
    nrows: int,
    ncols: int,
    context: SketchContext,
    params: LeastSquaresParams | None = None,
    alg: str = "qr",
    *,
    targets: int = 1,
    sparse: bool = False,
    stream_params=None,
    fault_plan=None,
    partition=None,
):
    """Out-of-core sketch-and-solve LS over ``(A_block, b_block)`` batches.

    The streaming face of :func:`approximate_least_squares`: same sketch
    selection (``sketch_type``/``sketch_size`` from ``params``, defaults
    CWT for sparse streams else JLT — FJLT has no columnwise partial-
    sketch rule), but ``S·A`` / ``S·b`` accumulate per batch through
    ``streaming.sketch_least_squares`` so A never needs to be resident.
    ``nrows``/``ncols`` are A's global shape (rows must be known up front
    to address the sketch's counter stream; ``io.scan_libsvm_dims`` scans
    them in one cheap pass).  ``stream_params`` is a
    :class:`~libskylark_tpu.streaming.StreamParams` (prefetch depth,
    checkpoint/resume).  Returns ``(x, info)``; when guarding is on
    (``SKYLARK_GUARD`` unset or truthy) ``info["recovery"]`` carries the
    guard's :class:`~libskylark_tpu.guard.RecoveryReport` dict — chunk
    replays of NaN-poisoned batches and small-solve fallbacks — and
    ``fault_plan`` (``nan_at``/``bad_sketch_at`` keyed by batch index)
    injects the faults the guard recovers from.

    ``partition`` (a :class:`~libskylark_tpu.streaming.RowPartition`)
    selects the multi-host elastic path: every process of a
    ``jax.distributed`` world calls this with the same arguments, each
    folds only its own row range, and the merged ``(x, info)`` comes
    back identical on every rank (``docs/distributed_streaming.md``).
    """
    from .. import policy, streaming

    params = params or LeastSquaresParams()
    decision = policy.consult(
        "ls_stream",
        m=nrows,
        n=ncols,
        targets=targets,
        dtype="float32",
        sparse=sparse,
        sketch_type=params.sketch_type,
        sketch_size=params.sketch_size,
        guard_on=guard.enabled(),
    )
    s = decision.sketch_size
    stype = decision.sketch_type
    S = create_sketch(stype, nrows, s, context)
    # The decision rides INTO the driver so info["policy"] is present in
    # the ledgered run_summary payload, not appended after it fired (the
    # telemetry acceptance contract: ledgered info keys == returned info
    # keys, and run_summary is the run's terminal ledger event).
    x, info = streaming.sketch_least_squares(
        source, S, ncols=ncols, targets=targets, alg=alg,
        params=stream_params, fault_plan=fault_plan, partition=partition,
        policy_decision=decision.to_dict(),
    )
    seconds = info.get("seconds") or 0.0
    policy.observe(
        decision,
        info,
        default_size=min(4 * ncols, nrows),
        rows_per_s=(info.get("rows", 0) / seconds) if seconds else None,
        batches=info.get("batches"),
    )
    # The driver's own run_summary fired before this observation existed;
    # flush again so the throughput lands in this run's profile write.
    policy.flush("streaming_lsq", info)
    return x, info
