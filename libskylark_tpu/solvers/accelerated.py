"""Sketch-to-precondition least squares: Blendenpik and LSRN.

≙ ``algorithms/regression/accelerated_linearl2_regression_solver_Elemental
.hpp:68-290`` and ``nla/least_squares.hpp:237-314`` (``FasterLeastSquares``):

- Blendenpik: S·A (columnwise sketch to a replicated s×n) → QR → R⁻¹ as
  right preconditioner → LSQR; if the preconditioner's condition estimate
  is bad, re-sketch with a larger sketch (the retry loop at ``:241-252``).
- LSRN: SVD of S·A → N = V·Σ⁻¹ as right preconditioner → LSQR.

TPU notes: the sketch is the sharded MXU-heavy op; QR/SVD of the s×n
sketch is replicated-small (the reference holds SA in ``[*,*]``).  The
retry loop is host control flow (each attempt changes shapes); each of its
stages launches one cached program.  The sketch is the planned apply
(``plans.apply``): ``PLAN_CACHE`` keys the executable on the sketch's JSON,
the shape, the dtype and the sharding, so a solve from the same context
state launches it again, while a context with advanced counters, or a
retry's larger ``s``, compiles one more.  The QR is ``jnp.linalg.qr``'s own
jit, the condition estimate the module-level jit ``_tri_condest`` with one
host read behind it.  A sparse A, a tracer and ``SKYLARK_NO_PLANS=1`` take
the eager apply (a ``bypass`` in ``plans.stats()``).  LSRN's sketch goes
the same way; its SVD preconditioner is built eagerly.  Each LSQR solve is
a single jitted while_loop.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl
import numpy as np

from .. import guard, plans, telemetry
from ..utils import profiling
from ..core.context import SketchContext
from ..core.params import Params
from ..sketch.base import Dimension, create_sketch
from .krylov import KrylovParams, lsqr
from .precond import MatPrecond, TriInversePrecond

__all__ = [
    "FasterLeastSquaresParams",
    "faster_least_squares",
    "lsrn_least_squares",
]


@dataclass
class FasterLeastSquaresParams(Params):
    """Knobs ≙ the reference's blendenpik/lsrn params structs."""

    # None → auto: FJLT for dense A, CWT for sparse (the reference's
    # dense/sparse split, accelerated_...Elemental.hpp:200-250).
    sketch_type: str | None = None
    gamma: float = 4.0  # sketch rows = gamma * n
    max_attempts: int = 3  # re-sketch retries (≙ :241-252)
    cond_threshold: float | None = None  # default 1/(10·eps^(1/2))
    krylov: KrylovParams | None = None


def _sketch_once(A, s, sketch_type, context):
    m = A.shape[0]
    S = create_sketch(sketch_type, m, s, context)
    SA = plans.apply(S, A, Dimension.COLUMNWISE)
    # a hash sketch of a BCOO operand is a BCOO; the s×n factorizations
    # below are dense (the reference holds SA in ``[*,*]``)
    return SA.todense() if hasattr(SA, "todense") else SA


@jax.jit
def _tri_condest(R):
    """1-norm condition estimate of upper-triangular R — ≙ the reference's
    ``utcondest`` (LAPACK ``trcon``-style, ``accelerated_...Elemental.hpp:
    25-66``): ‖R‖₁·‖R⁻¹‖₁ via a triangular solve against the identity.
    One program; the caller's ``float()`` is the host read."""
    n = R.shape[0]
    Rinv = jsl.solve_triangular(R, jnp.eye(n, dtype=R.dtype), lower=False)
    one_norm = lambda M: jnp.max(jnp.sum(jnp.abs(M), axis=0))
    return one_norm(R) * one_norm(Rinv)


def faster_least_squares(
    A,
    B,
    context: SketchContext,
    params: FasterLeastSquaresParams | None = None,
):
    """Blendenpik: near machine-precision LS at sketch-and-solve speed.

    Returns ``(X, info)``; ``info["attempts"]`` counts re-sketches and
    ``info["recovery"]`` is the guard-layer ledger of the retry loop
    (every re-sketch / SVD fallback as a :class:`~libskylark_tpu.guard.
    RecoveryAttempt`; ``guarded=False`` under ``SKYLARK_GUARD=0``, in
    which case the Blendenpik-native retry loop still runs — it predates
    the guard and is the paper's own robustness mechanism).
    """
    with telemetry.span("blendenpik"):
        params = params or FasterLeastSquaresParams()
        m, n = A.shape
        if m < n:
            raise ValueError(f"faster_least_squares needs tall A, got {A.shape}")
        eps = float(jnp.finfo(jnp.asarray(A).dtype if not hasattr(A, "todense") else A.data.dtype).eps)
        threshold = params.cond_threshold or 0.1 / np.sqrt(eps)

        guarded = guard.enabled()
        report = (
            guard.RecoveryReport(stage="blendenpik")
            if guarded
            else guard.RecoveryReport.disabled("blendenpik")
        )
        stype = params.sketch_type or (
            "CWT" if hasattr(A, "todense") else "FJLT"
        )
        gamma = params.gamma
        R = None
        for attempt in range(1, params.max_attempts + 1):
            s = min(int(gamma * n), m)
            with telemetry.span("blendenpik.sketch"):
                SA = _sketch_once(A, s, stype, context)
            with telemetry.span("blendenpik.factor"):
                R_try = jnp.linalg.qr(SA, mode="r")
            # 1-norm triangular condition estimate of the preconditioner, the
            # quantity the reference's retry loop consumes (``utcondest`` in
            # ``build_precond``, accelerated_...Elemental.hpp:68-77, 225-246).
            # (its float() is where the host waits for sketch, QR and estimate)
            with telemetry.span("blendenpik.condest"):
                cond = float(profiling.launch(_tri_condest, R_try))
            R = R_try
            good = np.isfinite(cond) and cond < threshold
            report.record(
                "initial" if attempt == 1 else "grow",
                verdict=guard.OK if good else guard.RESKETCH,
                cond=cond,
                sketch_size=s,
                detail="" if good else f"utcondest {cond:.3e} >= {threshold:.3e}",
            )
            if good:
                report.recovered = attempt > 1
                break
            gamma *= 2  # re-sketch larger (accelerated_...hpp:241-252)
        if not (np.isfinite(cond) and cond < threshold):
            # All attempts produced a bad preconditioner: fall back to the
            # exact SVD solver, as the reference does after its retry budget
            # (``_alt_solver``, accelerated_...Elemental.hpp:247-257, 275-280).
            from ..linalg.least_squares import exact_least_squares

            A_d = A.todense() if hasattr(A, "todense") else A
            with telemetry.span("blendenpik.fallback"):
                X = exact_least_squares(A_d, B, alg="svd")
            report.record(
                "fallback", verdict=guard.FALLBACK, detail="exact svd solve"
            )
            report.recovered = True
            info = {
                "attempts": attempt,
                "condest": cond,
                "fallback": "svd",
                "iterations": 0,
                "recovery": report.to_dict(),
            }
            telemetry.run_summary("blendenpik", info)
            return X, info
        precond = TriInversePrecond(R, lower=False)
        X, info = lsqr(A, B, precond=precond, params=params.krylov)
        if guarded:
            with telemetry.span("guard.check"):  # the host waits for LSQR here
                guard.check_finite(X, "blendenpik_lsqr", report=report)
        info["attempts"] = attempt
        info["condest"] = cond
        info["recovery"] = report.to_dict()
        telemetry.run_summary("blendenpik", info)
        return X, info


def lsrn_least_squares(
    A,
    B,
    context: SketchContext,
    params: FasterLeastSquaresParams | None = None,
):
    """LSRN: SVD-based preconditioning — robust for rank-deficient A
    (≙ ``lsrn_tag`` branch, ``accelerated_...Elemental.hpp:96-160``).

    Returns ``(X, info)``; under guarding (``SKYLARK_GUARD``, default on)
    a non-finite sketch climbs one fresh-seed resketch rung before the
    solve, the solution passes a finiteness sentinel, and
    ``info["recovery"]`` records the attempts.
    """
    with telemetry.span("lsrn"):
        params = params or FasterLeastSquaresParams()
        m, n = A.shape
        s = min(int(params.gamma * n), m)
        # LSRN wants a Gaussian-like sketch for its SVD preconditioner.
        stype = params.sketch_type or (
            "CWT" if hasattr(A, "todense") else "JLT"
        )
        guarded = guard.enabled()
        report = (
            guard.RecoveryReport(stage="lsrn")
            if guarded
            else guard.RecoveryReport.disabled("lsrn")
        )
        with telemetry.span("lsrn.sketch"):
            SA = _sketch_once(A, s, stype, context)
        with telemetry.span("guard.check"):
            bad_sketch = guarded and not guard.tree_all_finite(SA)
        if bad_sketch:
            # LSRN's SVD preconditioner absorbs ill conditioning by design, so
            # the only sketch pathology worth guarding here is non-finiteness.
            report.record(
                "initial", verdict=guard.RESKETCH, sketch_size=s,
                detail="non-finite sketch output",
            )
            with telemetry.span("lsrn.sketch"):
                SA = _sketch_once(
                    A, s, stype, guard.derived_context(context, 1)
                )
            report.record("resketch", verdict=guard.OK, sketch_size=s)
            with telemetry.span("guard.check"):
                guard.check_finite(SA, "lsrn_sketch", report=report)
            report.recovered = True
        elif guarded:
            report.record("initial", verdict=guard.OK, sketch_size=s)
        with telemetry.span("lsrn.factor"):
            _, sv, Vt = jnp.linalg.svd(SA, full_matrices=False)
            eps = jnp.finfo(sv.dtype).eps
            cutoff = sv[0] * eps * max(SA.shape)
            sinv = jnp.where(sv > cutoff, 1.0 / sv, 0.0)
            N = Vt.T * sinv[None, :]  # V·Σ⁻¹
        X, info = lsqr(A, B, precond=MatPrecond(N), params=params.krylov)
        if guarded:
            with telemetry.span("guard.check"):
                guard.check_finite(X, "lsrn_lsqr", report=report)
        info["recovery"] = report.to_dict()
        telemetry.run_summary("lsrn", info)
        return X, info
