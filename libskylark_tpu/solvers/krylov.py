"""Krylov solvers as jitted ``lax.while_loop`` iterations.

≙ ``algorithms/Krylov/``: LSQR (``LSQR.hpp:21-259``), preconditioned CG
(``CG.hpp:24-150``), FlexibleCG (``FlexibleCG.hpp:23``), Chebyshev
(``Chebyshev.hpp``), with ``krylov_iter_params_t``
(``krylov_iter_params.hpp:8``) as a dataclass.

TPU design:

- Everything runs inside one ``lax.while_loop`` — convergence tests are
  computed on-device (no per-iteration host sync, unlike the reference's
  rank-0 logging round-trips).  The hot ops are the two matvecs per
  iteration, which for sharded A are GSPMD matmuls with psum reductions
  over ICI (≙ the MPI allreduces inside Elemental's Gemv).
- The loop is ONE module-level jitted program, :func:`run`: each solver's
  body is a module-level function of ``(state, operands)`` and A, the
  preconditioner and the tolerances are its arguments, so a second solve
  at the same shapes dispatches the first one's executable from
  ``jax.jit``'s cache instead of tracing and lowering its own while the
  device waits.  Operators and preconditioners that are callables take
  the per-solve path of :func:`_lifted_stepper`.
- All solvers are **multi-RHS**: B may be (m,) or (m, k); the Golub-Kahan /
  CG scalars become per-column vectors (the reference iterates columns
  together the same way, via Elemental matrices of width k).
- Stopping: per-column Paige-Saunders S1/S2 tests plus the reference's
  stagnation detector (``LSQR.hpp:193-230``); the loop exits when every
  column has converged or stagnated.

Preemption safety: every solver is structured as a ``*_chunked`` factory
returning a :class:`~libskylark_tpu.resilient.chunked.ChunkedSolver` —
``init_state()`` builds the loop carry, ``step_chunk(state, k)`` runs one
jitted while-loop segment of ≤ k iterations, ``extract_result(state)``
finishes.  The classic one-shot entry points (``lsqr`` etc.) run a single
chunk of the full ``iter_lim`` budget, so they keep their exact semantics;
``resilient.ResilientRunner`` drives the same factories in checkpointed
host rounds.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import telemetry
from ..core.params import Params
from ..resilient.chunked import ChunkedSolver
from ..utils import profiling
from .precond import IdPrecond

__all__ = [
    "KrylovParams",
    "lsqr",
    "cg",
    "flexible_cg",
    "chebyshev",
    "lsqr_chunked",
    "cg_chunked",
    "flexible_cg_chunked",
    "chebyshev_chunked",
]


@dataclass
class KrylovParams(Params):
    """≙ ``krylov_iter_params_t`` (tolerance, iter_lim)."""

    tolerance: float = 1e-14
    iter_lim: int = 100


def _scoped(name, f):
    """``f`` with its operations under the named scope ``name``: the
    metadata that ``benchmarks/scope_reduce.py`` reads device time by."""
    def g(*args):
        with jax.named_scope(name):
            return f(*args)

    return g


def _ops(A):
    """(matvec, rmatvec) for dense / BCOO / (matvec, rmatvec) pair, under
    the scopes ``krylov.matvec`` and ``krylov.rmatvec``."""
    matvec, rmatvec = (
        A if isinstance(A, tuple) else ((lambda x: A @ x), (lambda y: A.T @ y))
    )
    return _scoped("krylov.matvec", matvec), _scoped("krylov.rmatvec", rmatvec)


def _colnorm(X):
    return jnp.sqrt(jnp.sum(X * X, axis=0))


def _as2d(b):
    b = jnp.asarray(b)
    return (b[:, None], True) if b.ndim == 1 else (b, False)


def _segment(s, num_iters, iter_lim, step, done_of):
    """≤ num_iters iterations of ``step`` over the carry dict ``s``, which
    holds a global ``it`` counter.  ``done_of(state)`` adds the solver's
    on-device convergence predicate to the loop condition."""
    stop = jnp.minimum(s["it"] + num_iters, iter_lim)

    def cond(st):
        go = st["it"] < stop
        if done_of is not None:
            go = go & ~done_of(st)
        return go

    return lax.while_loop(cond, step, s)


@partial(jax.jit, static_argnames=("body", "done_of"))
def run(s, operands, num_iters, iter_lim, *, body, done_of):
    """The while-loop segment of every solver whose operands are arrays.

    Built once per (``body``, shapes, dtypes, structure of ``operands``)
    and dispatched from ``jax.jit``'s own in-memory cache after that: the
    statics are module-level functions, the same objects in every call,
    and the budget is two scalars, so one executable serves every chunk
    length.  ``operands`` is the pytree of everything ``body`` reads that
    is an array — the operator A (dense or BCOO), the preconditioner's
    factor, the tolerances — so they are arguments of the program, never
    literals in it (at 262144x1024 f32 a segment that closed over A was
    a 2 GB executable that took minutes to compile on the chip and was
    too large for the persistent cache to hold), and nothing that
    outlives the call holds a device array."""
    return _segment(
        s, num_iters, iter_lim, lambda st: body(st, operands), done_of
    )


def _all_arrays(operands) -> bool:
    return all(
        isinstance(x, (jax.Array, np.ndarray)) for x in jax.tree.leaves(operands)
    )


def _chunk_stepper(body, operands, iter_lim: int, done_of=None):
    """``step_chunk(state, k)`` for the module-level ``body(state,
    operands)``.  The path follows what the operands are: a pytree of
    arrays rides the cached :func:`run`; anything opaque in it — a
    ``(matvec, rmatvec)`` pair, a ``precond(R, it)`` function, an object
    that is no registered pytree — takes :func:`_lifted_stepper`, which
    builds a segment of its own per solve."""
    if not _all_arrays(operands):
        return _lifted_stepper(lambda s: body(s, operands), iter_lim, done_of)

    def step_chunk(s, num_iters: int):
        # the first call at a shape: trace, lower, cache key, fetch; every
        # later one: a dispatch from jit's cache
        with telemetry.span("krylov.segment"):
            return profiling.launch(
                run, s, operands, num_iters, iter_lim, body=body, done_of=done_of
            )

    return step_chunk


def _lifted_stepper(body, iter_lim: int, done_of):
    """The segment for a ``body(state)`` that closes over callables.

    The arrays reachable through them cannot be named, so they are
    lifted out: the body is traced once to a jaxpr, whose constants they
    are, and passed to a segment jitted for this solve as real arguments
    (closed over by the jit they would be literals of the executable).
    The jit and the jaxpr die with the solver: a cache keyed on the
    closure would pin what it closes over for the life of the process."""
    lifted: list = []  # [jaxpr of body, output tree]
    consts: list = []  # the jaxpr's constants: the segment never closes over them

    def body_of(st, consts):
        jaxpr, out_tree = lifted
        out = jax.core.eval_jaxpr(jaxpr, consts, *jax.tree.leaves(st))
        return jax.tree.unflatten(out_tree, out)

    @partial(jax.jit, static_argnames=("num_iters",))
    def run(s, consts, num_iters: int):
        return _segment(
            s, num_iters, iter_lim, lambda st: body_of(st, consts), done_of
        )

    def step_chunk(s, num_iters: int):
        if not lifted:
            with telemetry.span("krylov.lift"):
                closed, out_shape = jax.make_jaxpr(body, return_shape=True)(s)
            lifted.extend((closed.jaxpr, jax.tree.structure(out_shape)))
            consts.extend(closed.consts)
        # trace, lower, cache key, fetch and enqueue of the segment
        with telemetry.span("krylov.segment"):
            return profiling.launch(run, s, consts, num_iters=num_iters)

    return step_chunk


def _all_done(st):
    return jnp.all(st["done"])


def _one_shot(factory_state_solver, iter_lim: int):
    sol = factory_state_solver
    with telemetry.span("krylov.init"):  # eager: one pass over A
        state = sol.init_state()
    state = sol.step_chunk(state, max(iter_lim, 0))
    with telemetry.span("krylov.result"):
        return sol.extract_result(state)


def _preconditioned(A, N):
    """(matvec, rmatvec) of A·N for a right preconditioner N."""
    matvec0, rmatvec0 = _ops(A)
    apply = _scoped("krylov.precond", N.apply)
    apply_adjoint = _scoped("krylov.precond", N.apply_adjoint)
    return (
        lambda v: matvec0(apply(v)),
        lambda u: apply_adjoint(rmatvec0(u)),
    )


def _lsqr_body(s, operands):
    """One LSQR iteration on A·N; ``operands = (A, N, tol)``."""
    A, N, tol = operands
    matvec, rmatvec = _preconditioned(A, N)
    atol = btol = tol
    eps = jnp.finfo(tol.dtype).eps
    U, V, W, Y = s["U"], s["V"], s["W"], s["Y"]
    alpha, beta = s["alpha"], s["beta"]
    # Golub-Kahan bidiagonalization step (LSQR.hpp:100-130).
    U = matvec(V) - alpha[None, :] * U
    beta = _colnorm(U)
    U = U / jnp.where(beta > 0, beta, 1)
    V = rmatvec(U) - beta[None, :] * V
    alpha_new = _colnorm(V)
    V = V / jnp.where(alpha_new > 0, alpha_new, 1)
    # Givens rotation update (LSQR.hpp:135-160).  rho can be 0 for an
    # all-zero RHS column (alpha=beta=0); guard every division so the
    # column stays exactly 0 instead of NaN-poisoning Y.
    rho = jnp.hypot(s["rhobar"], beta)
    rho_s = jnp.where(rho > 0, rho, 1)
    c = s["rhobar"] / rho_s
    sn = beta / rho_s
    theta = sn * alpha_new
    rhobar = -c * alpha_new
    phi = c * s["phibar"]
    phibar_new = sn * s["phibar"]
    step = jnp.where(s["done"], 0.0, phi / rho_s)
    Y = Y + step[None, :] * W
    W = V - (theta / rho_s)[None, :] * W
    anorm = jnp.hypot(s["anorm"], jnp.max(jnp.hypot(alpha, beta)))
    # Paige-Saunders S1/S2 per column (LSQR.hpp:193-230).
    rnorm = phibar_new
    arnorm = alpha_new * jnp.abs(c * phibar_new)
    ynorm = _colnorm(Y)
    s1 = rnorm <= btol * s["bnorm"] + atol * anorm * ynorm
    s2 = arnorm <= atol * anorm * jnp.maximum(rnorm, eps)
    # Stagnation (LSQR.hpp stagnation check): for LS problems the
    # residual plateaus at the optimum while the normal-equation
    # residual (arnorm) keeps falling, so stagnation requires BOTH to
    # stop improving for several consecutive iterations.
    no_progress = (phibar_new >= s["phibar"] * (1 - 10 * eps)) & (
        arnorm >= s["arnorm_best"] * (1 - 1e3 * eps)
    )
    stag = jnp.where(no_progress, s["stag"] + 1, 0)
    done = s["done"] | s1 | s2 | (stag >= 5)
    return dict(
        it=s["it"] + 1,
        Y=Y,
        U=U,
        V=V,
        W=W,
        alpha=alpha_new,
        beta=beta,
        rhobar=rhobar,
        phibar=phibar_new,
        anorm=anorm,
        done=done,
        stag=stag,
        arnorm_best=jnp.minimum(s["arnorm_best"], arnorm),
        bnorm=s["bnorm"],
    )


def lsqr_chunked(
    A, B, precond=None, params: KrylovParams | None = None, x0=None
) -> ChunkedSolver:
    """Chunkable LSQR: state in/out per ≤ k-iteration jitted segment (see
    :func:`lsqr` for the math and return convention of the result)."""
    params = params or KrylovParams()
    N = precond or IdPrecond()
    matvec0, _ = _ops(A)
    _, rmatvec = _preconditioned(A, N)

    B, squeeze = _as2d(B)
    dtype = B.dtype
    eps = jnp.finfo(dtype).eps
    tol = jnp.asarray(max(params.tolerance, float(eps)), dtype)

    if x0 is not None:
        x0 = jnp.asarray(x0)
        if x0.ndim == 1:
            x0 = x0[:, None]

    def init_state():
        U = B if x0 is None else B - matvec0(x0)
        beta = _colnorm(U)
        U = U / jnp.where(beta > 0, beta, 1)
        V = rmatvec(U)
        alpha = _colnorm(V)
        V = V / jnp.where(alpha > 0, alpha, 1)
        n = V.shape[0]
        k = B.shape[1]
        return dict(
            it=jnp.zeros((), jnp.int32),
            Y=jnp.zeros((n, k), dtype),
            U=U,
            V=V,
            W=V,
            alpha=alpha,
            beta=beta,
            rhobar=alpha,
            phibar=beta,
            anorm=jnp.zeros((), dtype),
            done=beta <= tol * _colnorm(B),
            stag=jnp.zeros((k,), jnp.int32),
            arnorm_best=jnp.full((k,), jnp.inf, dtype),
            bnorm=_colnorm(B),
        )

    def extract_result(s):
        X = N.apply(s["Y"])
        if x0 is not None:
            X = X + x0
        info = {
            "iterations": s["it"],
            "flag": jnp.where(jnp.all(s["done"]), 0, 1),
            "resid": s["phibar"],
        }
        return (X[:, 0] if squeeze else X), info

    return ChunkedSolver(
        init_state=init_state,
        step_chunk=_chunk_stepper(
            _lsqr_body, (A, N, tol), params.iter_lim, done_of=_all_done
        ),
        extract_result=extract_result,
        is_done=lambda s: int(s["it"]) >= params.iter_lim
        or bool(jnp.all(s["done"])),
        iteration=lambda s: int(s["it"]),
        kind="lsqr",
    )


def lsqr(A, B, precond=None, params: KrylovParams | None = None, x0=None):
    """Preconditioned LSQR for ``min_X ||A X - B||`` (per column).

    ``precond`` is a *right* preconditioner N (≙ ``outplace_precond_t``):
    LSQR runs on A·N and returns ``X = N·Y`` (Blendenpik/LSRN use this).
    Returns ``(X, info)`` with ``info = {"iterations", "flag", "resid"}``;
    flag 0 = converged, 1 = iter limit, per column 2 = stagnated.
    """
    params = params or KrylovParams()
    return _one_shot(lsqr_chunked(A, B, precond, params, x0), params.iter_lim)


def _spd_matvec(A):
    """CG's product with A.  A dense A is multiplied at ``highest``: the
    default f32 product on a TPU rounds A and P to bfloat16, which is
    another system than the stated one (PERF.md section 6, PR 31).
    LSQR's products are not these (:func:`_ops`)."""
    if isinstance(A, (jax.Array, np.ndarray)):
        return _scoped(
            "krylov.matvec", lambda x: jnp.dot(A, x, precision="highest"))
    return _ops(A)[0]


def _cg_body(s, operands):
    """One preconditioned CG iteration; ``operands = (A, M, tol, bnorm)``."""
    A, M, tol, bnorm = operands
    matvec = _spd_matvec(A)
    Q = matvec(s["P"])
    denom = jnp.sum(s["P"] * Q, axis=0)
    alpha = jnp.where(s["done"], 0.0, s["rz"] / jnp.where(denom != 0, denom, 1))
    X = s["X"] + alpha[None, :] * s["P"]
    R = s["R"] - alpha[None, :] * Q
    with jax.named_scope("krylov.precond"):
        Z = M.apply(R)
    rz_new = jnp.sum(R * Z, axis=0)
    beta = rz_new / jnp.where(s["rz"] != 0, s["rz"], 1)
    P = Z + beta[None, :] * s["P"]
    done = s["done"] | (_colnorm(R) <= tol * jnp.maximum(bnorm, 1e-30))
    return dict(it=s["it"] + 1, X=X, R=R, P=P, rz=rz_new, done=done)


def cg_chunked(
    A, B, precond=None, params: KrylovParams | None = None, x0=None
) -> ChunkedSolver:
    """Chunkable preconditioned CG (see :func:`cg`)."""
    params = params or KrylovParams()
    M = precond or IdPrecond()
    matvec = _spd_matvec(A)
    B, squeeze = _as2d(B)
    dtype = B.dtype
    tol = jnp.asarray(params.tolerance, dtype)
    bnorm = _colnorm(B)

    def init_state():
        X = jnp.zeros_like(B) if x0 is None else jnp.asarray(x0).reshape(B.shape)
        R = B - matvec(X) if x0 is not None else B
        Z = M.apply(R)
        return dict(
            it=jnp.zeros((), jnp.int32),
            X=X,
            R=R,
            P=Z,
            rz=jnp.sum(R * Z, axis=0),
            done=_colnorm(R) <= tol * jnp.maximum(bnorm, 1e-30),
        )

    def extract_result(s):
        info = {
            "iterations": s["it"],
            "flag": jnp.where(jnp.all(s["done"]), 0, 1),
            "resid": _colnorm(s["R"]),
        }
        return (s["X"][:, 0] if squeeze else s["X"]), info

    return ChunkedSolver(
        init_state=init_state,
        step_chunk=_chunk_stepper(
            _cg_body, (A, M, tol, bnorm), params.iter_lim, done_of=_all_done
        ),
        extract_result=extract_result,
        is_done=lambda s: int(s["it"]) >= params.iter_lim
        or bool(jnp.all(s["done"])),
        iteration=lambda s: int(s["it"]),
        kind="cg",
    )


def cg(A, B, precond=None, params: KrylovParams | None = None, x0=None):
    """Preconditioned conjugate gradient for SPD ``A X = B`` (multi-RHS).

    ≙ ``algorithms/Krylov/CG.hpp:24-150`` (with ``precond`` the outplace
    M ≈ A⁻¹ as in ``FasterKernelRidge``'s feature-map preconditioner).
    """
    params = params or KrylovParams()
    return _one_shot(cg_chunked(A, B, precond, params, x0), params.iter_lim)


def _fcg_body(s, operands):
    """One FlexibleCG iteration; ``operands = (A, M, tol, bnorm)`` with M
    a preconditioner object or a ``(R, it) -> Z`` function."""
    A, M, tol, bnorm = operands
    matvec, _ = _ops(A)
    memory = s["Pbuf"].shape[0]
    with jax.named_scope("krylov.precond"):
        Z = M.apply(s["R"]) if hasattr(M, "apply") else M(s["R"], s["it"])
    # Orthogonalize Z against stored directions (A-inner product).
    coeffs = jnp.einsum("smk,mk->sk", s["Qbuf"], Z) / s["pq"]
    P = Z - jnp.einsum("smk,sk->mk", s["Pbuf"], coeffs)
    Q = matvec(P)
    denom = jnp.sum(P * Q, axis=0)
    denom = jnp.where(jnp.abs(denom) > 0, denom, 1)
    alpha = jnp.where(s["done"], 0.0, jnp.sum(P * s["R"], axis=0) / denom)
    X = s["X"] + alpha[None, :] * P
    R = s["R"] - alpha[None, :] * Q
    slot = s["it"] % memory
    Pbuf = s["Pbuf"].at[slot].set(P)
    Qbuf = s["Qbuf"].at[slot].set(Q)
    pq = s["pq"].at[slot].set(denom)
    done = s["done"] | (_colnorm(R) <= tol * jnp.maximum(bnorm, 1e-30))
    return dict(it=s["it"] + 1, X=X, R=R, Pbuf=Pbuf, Qbuf=Qbuf, pq=pq, done=done)


def flexible_cg_chunked(
    A, B, precond=None, params: KrylovParams | None = None, memory: int = 5
) -> ChunkedSolver:
    """Chunkable FlexibleCG (see :func:`flexible_cg`).  The ring buffers of
    past directions ride the state pytree, so a resumed run keeps the same
    re-orthogonalization window."""
    params = params or KrylovParams()
    B, squeeze = _as2d(B)
    dtype = B.dtype
    tol = jnp.asarray(params.tolerance, dtype)
    m, k = B.shape
    M = IdPrecond() if precond is None else precond
    bnorm = _colnorm(B)

    def init_state():
        return dict(
            it=jnp.zeros((), jnp.int32),
            X=jnp.zeros_like(B),
            R=B,
            # Ring buffers of past directions P and A·P, per RHS column.
            Pbuf=jnp.zeros((memory, m, k), dtype),
            Qbuf=jnp.zeros((memory, m, k), dtype),
            pq=jnp.ones((memory, k), dtype),  # pᵀAp normalizers (1 avoids 0-div)
            done=bnorm <= tol,
        )

    def extract_result(s):
        info = {
            "iterations": s["it"],
            "flag": jnp.where(jnp.all(s["done"]), 0, 1),
            "resid": _colnorm(s["R"]),
        }
        return (s["X"][:, 0] if squeeze else s["X"]), info

    return ChunkedSolver(
        init_state=init_state,
        step_chunk=_chunk_stepper(
            _fcg_body, (A, M, tol, bnorm), params.iter_lim, done_of=_all_done
        ),
        extract_result=extract_result,
        is_done=lambda s: int(s["it"]) >= params.iter_lim
        or bool(jnp.all(s["done"])),
        iteration=lambda s: int(s["it"]),
        kind="flexible_cg",
    )


def flexible_cg(
    A, B, precond=None, params: KrylovParams | None = None, memory: int = 5
):
    """Flexible CG: supports a *varying* preconditioner by re-orthogonalizing
    the search direction against the last ``memory`` directions.

    ≙ ``algorithms/Krylov/FlexibleCG.hpp:23`` (used with the inexact/
    randomized inner preconditioners of AsyFCG, ``algorithms/asynch/
    AsyFCG.hpp``).  ``precond`` may be a function ``(R, it) -> Z`` for
    iteration-dependent preconditioning, or a fixed precond object.
    """
    params = params or KrylovParams()
    return _one_shot(
        flexible_cg_chunked(A, B, precond, params, memory), params.iter_lim
    )


def _chebyshev_body(s, operands):
    """One Chebyshev step; ``operands = (A, B, d, c)``, the interval's
    centre and half-width in B's dtype."""
    A, B, d, c = operands
    matvec, _ = _ops(A)
    dtype = B.dtype
    i, X, Xprev = s["it"], s["X"], s["Xprev"]
    R = B - matvec(X)
    alpha = jnp.where(
        i == 0,
        1.0 / d,
        jnp.where(
            i == 1,
            d / (d * d - c * c / 2),
            1.0 / (d - s["alpha"] * c * c / 4),
        ),
    ).astype(dtype)
    beta = jnp.where(i == 0, 0.0, alpha * d - 1.0).astype(dtype)
    Xnew = X + alpha * R + beta * (X - Xprev)
    return dict(it=i + 1, X=Xnew, Xprev=X, alpha=alpha)


def chebyshev_chunked(
    A, B, sigma_lo: float, sigma_hi: float, params: KrylovParams | None = None
) -> ChunkedSolver:
    """Chunkable Chebyshev semi-iteration (see :func:`chebyshev`).  The
    recurrence depends only on the absolute iteration index, which rides
    the state, so chunk boundaries don't disturb the polynomial."""
    params = params or KrylovParams()
    B, squeeze = _as2d(B)
    dtype = B.dtype
    d = jnp.asarray((sigma_hi + sigma_lo) / 2, dtype)
    c = jnp.asarray((sigma_hi - sigma_lo) / 2, dtype)

    def init_state():
        X0 = jnp.zeros_like(B)
        return dict(
            it=jnp.zeros((), jnp.int32),
            X=X0,
            Xprev=X0,
            alpha=jnp.asarray(0, dtype),
        )

    def extract_result(s):
        info = {"iterations": s["it"], "flag": jnp.asarray(0)}
        return (s["X"][:, 0] if squeeze else s["X"]), info

    return ChunkedSolver(
        init_state=init_state,
        step_chunk=_chunk_stepper(_chebyshev_body, (A, B, d, c), params.iter_lim),
        extract_result=extract_result,
        is_done=lambda s: int(s["it"]) >= params.iter_lim,
        iteration=lambda s: int(s["it"]),
        kind="chebyshev",
    )


def chebyshev(A, B, sigma_lo: float, sigma_hi: float, params: KrylovParams | None = None):
    """Chebyshev semi-iteration for SPD ``A X = B`` given eigenvalue bounds
    ``[sigma_lo, sigma_hi]`` (≙ ``algorithms/Krylov/Chebyshev.hpp`` — the
    reference also takes singular-value bounds).  No inner products — the
    TPU-friendliest Krylov method (no reductions → no collectives at all
    for row-sharded A beyond the matvec itself).
    """
    params = params or KrylovParams()
    return _one_shot(
        chebyshev_chunked(A, B, sigma_lo, sigma_hi, params), params.iter_lim
    )
