"""The Krylov while-loop segment is built once a shape and never holds A.

``solvers.krylov.run`` is one module-level ``jax.jit``: its arguments are
the carry and the operands pytree (operator, preconditioner, tolerances),
its statics the solver's module-level body.  Here: a second solve at the
same shapes traces and lowers nothing and answers with the same bytes; a
new shape or dtype adds exactly one entry to ``run``'s cache; a second
problem of the same shapes is solved with its own A and R; the lowered
program holds no literal of A's size and nothing that outlives a solve
holds a device array.  Operands the stepper cannot see through (callables,
unregistered objects) keep the per-solve lifted segment, and a checkpoint
written by the parent's carry layout resumes.
"""

import gc
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _builds import builds
from jax.experimental import sparse as jsparse

from libskylark_tpu import SketchContext
from libskylark_tpu.ml import GaussianKernel, KrrParams, faster_kernel_ridge
from libskylark_tpu.resilient import (
    FaultPlan,
    ResilientParams,
    ResilientRunner,
    SimulatedPreemption,
)
from libskylark_tpu.solvers import (
    IdPrecond,
    KrylovParams,
    MatPrecond,
    TriInversePrecond,
    cg,
    cg_chunked,
    chebyshev,
    flexible_cg,
    krylov,
    lsqr,
    lsqr_chunked,
)

M, N = 83, 7  # shapes no other test file solves at
DATA = os.path.join(os.path.dirname(__file__), "data", "krylov_parent_carry")


def tall(seed, m=M, n=N, dtype=jnp.float64):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((m, n)), dtype)


def spd_of(A):
    return A.T @ A + jnp.eye(A.shape[1], dtype=A.dtype)


def rhs(seed, rows, ndim, dtype=jnp.float64):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((rows,) if ndim == 1 else (rows, 3)), dtype)


def r_factor(A):
    return jnp.linalg.qr(A, mode="r")


def _lsqr_with(make_precond):
    def solve(A, b):
        return lsqr(A, b, precond=make_precond(A), params=KrylovParams(iter_lim=60))[0]

    return solve


def _cg(A, b):
    return cg(spd_of(A), b[: A.shape[1]], params=KrylovParams(iter_lim=60))[0]


def _cg_mat(A, b):
    S = spd_of(A)
    return cg(S, b[: A.shape[1]], precond=MatPrecond(jnp.linalg.inv(S)),
              params=KrylovParams(iter_lim=60))[0]


def _flexible_cg(A, b):
    S = spd_of(A)
    return flexible_cg(S, b[: A.shape[1]], precond=MatPrecond(jnp.diag(1 / jnp.diag(S))),
                       params=KrylovParams(iter_lim=60))[0]


def _chebyshev(A, b):
    return chebyshev(spd_of(A), b[: A.shape[1]], 1.0, 400.0, KrylovParams(iter_lim=25))[0]


def _lsqr_bcoo(A, b):
    return lsqr(jsparse.BCOO.fromdense(A), b, params=KrylovParams(iter_lim=60))[0]


SOLVES = {
    "lsqr-id": _lsqr_with(lambda A: IdPrecond()),
    "lsqr-mat": _lsqr_with(lambda A: MatPrecond(jnp.linalg.inv(r_factor(A)))),
    "lsqr-tri": _lsqr_with(lambda A: TriInversePrecond(r_factor(A))),
    "lsqr-bcoo": _lsqr_bcoo,
    "cg": _cg,
    "cg-mat": _cg_mat,
    "flexible_cg-mat": _flexible_cg,
    "chebyshev": _chebyshev,
}


@pytest.fixture
def empty_cache():
    krylov.run.clear_cache()
    yield
    krylov.run.clear_cache()


@pytest.mark.parametrize("ndim", [1, 2])
@pytest.mark.parametrize("solver", SOLVES)
def test_a_second_solve_builds_nothing_and_answers_the_same(solver, ndim, empty_cache):
    solve = SOLVES[solver]
    A, b = tall(1), rhs(2, M, ndim)
    cold = np.asarray(solve(A, b))
    assert krylov.run._cache_size() == 1
    with builds() as seen:
        warm = np.asarray(solve(A, b))
    if solver != "lsqr-bcoo":  # BCOO's eager products re-trace closures of their own
        assert seen == []  # not the segment, not an eager op around it
    assert krylov.run._cache_size() == 1
    assert warm.tobytes() == cold.tobytes()
    assert warm.ndim == ndim


@pytest.mark.parametrize("solver", ["lsqr-id", "lsqr-mat", "lsqr-tri", "cg", "chebyshev"])
def test_a_new_shape_or_dtype_adds_exactly_one(solver, empty_cache):
    solve = SOLVES[solver]
    solve(tall(1), rhs(2, M, 1))
    assert krylov.run._cache_size() == 1
    solve(tall(1, M + 8, N + 2), rhs(2, M + 8, 1))
    assert krylov.run._cache_size() == 2
    solve(tall(1, dtype=jnp.float32), rhs(2, M, 1, jnp.float32))
    assert krylov.run._cache_size() == 3
    solve(tall(3), rhs(4, M, 1))  # other values, the shapes of the first
    assert krylov.run._cache_size() == 3


def test_every_budget_runs_the_one_executable(empty_cache):
    """The chunk length is an argument: a runner's short last chunk and
    another ``iter_lim`` dispatch the program the first chunk built."""
    A, B = tall(1), rhs(2, M, 2)
    one, _ = lsqr(A, B, params=KrylovParams(iter_lim=30))
    chunked, _ = ResilientRunner(
        lsqr_chunked(A, B, params=KrylovParams(iter_lim=30)),
        ResilientParams(checkpoint_every=7),
    ).run()
    assert np.asarray(one).tobytes() == np.asarray(chunked).tobytes()
    lsqr(A, B, params=KrylovParams(iter_lim=3))
    assert krylov.run._cache_size() == 1


@pytest.mark.parametrize("precond", ["tri", "mat"])
def test_the_second_problem_is_solved_with_its_own_operands(precond, empty_cache):
    """Same shapes, other A and R: a cached segment that kept the first
    call's operands would answer the first problem again."""
    make = {"tri": lambda A: TriInversePrecond(r_factor(A)),
            "mat": lambda A: MatPrecond(jnp.linalg.inv(r_factor(A)))}[precond]
    answers = []
    for seed in (1, 5):
        A, b = tall(seed), rhs(seed + 1, M, 1)
        x, _ = lsqr(A, b, precond=make(A), params=KrylovParams(iter_lim=60))
        want = np.linalg.lstsq(np.asarray(A), np.asarray(b), rcond=None)[0]
        np.testing.assert_allclose(np.asarray(x), want, rtol=1e-9, atol=1e-11)
        answers.append(want)
    assert krylov.run._cache_size() == 1
    assert np.abs(answers[0] - answers[1]).max() > 1e-2


def test_the_segment_takes_a_and_r_as_arguments_not_literals():
    m, n = 512, 64  # A is 128 KiB of f32: a literal of it is > 256 KB of text
    A = tall(1, m, n, jnp.float32)
    sol = lsqr_chunked(A, rhs(2, m, 1, jnp.float32), precond=TriInversePrecond(r_factor(A)))
    operands = (A, TriInversePrecond(r_factor(A)), jnp.asarray(1e-6, jnp.float32))
    text = krylov.run.lower(
        sol.init_state(), operands, 10, 10,
        body=krylov._lsqr_body, done_of=krylov._all_done,
    ).as_text()
    assert len(text) < 60_000
    for line in text.splitlines():
        if "constant" in line:
            assert f"tensor<{m}x{n}x" not in line and f"tensor<{n}x{n}x" not in line
    assert f"tensor<{m}x{n}xf32>" in text  # A is there, as a parameter


def test_nothing_the_stepper_keeps_holds_a_device_array():
    def solve(seed):
        A, b = tall(seed, 256, 16), rhs(seed + 1, 256, 1)
        x, info = lsqr(A, b, precond=TriInversePrecond(r_factor(A)),
                       params=KrylovParams(iter_lim=40))
        return float(jnp.sum(x)) + int(info["iterations"])

    solve(1)  # whatever JAX caches for the process is cached now
    gc.collect()
    before = len(jax.live_arrays())
    solve(3)
    gc.collect()
    assert len(jax.live_arrays()) == before


# -- operands the stepper cannot see through: the lifted segment of old ------


class _PlainJacobi:
    """No registered pytree: the stepper sees one opaque leaf."""

    def __init__(self, S):
        self.d = jnp.diag(S)

    def apply(self, R):
        return R / self.d[:, None]


def test_a_pair_of_callables_as_the_operator(empty_cache):
    A, b = tall(1), rhs(2, M, 1)
    R = r_factor(A)
    want, _ = lsqr(A, b, precond=TriInversePrecond(R), params=KrylovParams(iter_lim=60))
    size = krylov.run._cache_size()
    got, info = lsqr(((lambda x: A @ x), (lambda y: A.T @ y)), b,
                     precond=TriInversePrecond(R), params=KrylovParams(iter_lim=60))
    assert krylov.run._cache_size() == size  # not through the cached segment
    assert int(info["flag"]) == 0
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


def test_an_unregistered_preconditioner_and_a_function_of_the_iteration(empty_cache):
    S, b = spd_of(tall(1)), rhs(2, N, 1)
    kp = KrylovParams(iter_lim=80, tolerance=1e-12)
    want, _ = cg(S, b, precond=MatPrecond(jnp.diag(1 / jnp.diag(S))), params=kp)
    size = krylov.run._cache_size()
    got, _ = cg(S, b, precond=_PlainJacobi(S), params=kp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-9)
    d = jnp.diag(S)
    varying, info = flexible_cg(
        S, b, precond=lambda R, it: R / (d[:, None] * (1 + 1e-3 * jnp.cos(1.0 * it))),
        params=kp)
    assert int(info["flag"]) == 0
    np.testing.assert_allclose(np.asarray(S @ varying), np.asarray(b), rtol=1e-7, atol=1e-9)
    assert krylov.run._cache_size() == size


def test_the_lifted_segment_is_dropped_with_its_solver():
    """The per-solve jit closes over A through the body: nothing may keep
    it once the solver is gone."""
    def solve(seed):
        A, b = tall(seed, 256, 16), rhs(seed + 1, 256, 1)
        x, _ = lsqr(((lambda v: A @ v), (lambda u: A.T @ u)), b,
                    params=KrylovParams(iter_lim=40))
        return float(jnp.sum(x))

    solve(1)
    gc.collect()
    before = len(jax.live_arrays())
    solve(3)
    gc.collect()
    assert len(jax.live_arrays()) == before


@pytest.mark.parametrize("checkpointed", [False, True])
def test_faster_kernel_ridge_with_its_feature_map_preconditioner(checkpointed, tmp_path):
    rng = np.random.default_rng(4)
    X = jnp.asarray(rng.standard_normal((60, 4)))
    y = jnp.asarray(rng.standard_normal(60))
    params = KrrParams(tolerance=1e-10, iter_lim=200)
    if checkpointed:
        params = KrrParams(tolerance=1e-10, iter_lim=200,
                           checkpoint_dir=str(tmp_path), checkpoint_every=5)
    k = GaussianKernel(4, 2.0)
    model = faster_kernel_ridge(k, X, y, 0.1, 128, SketchContext(seed=4), params)
    K = np.asarray(k.gram(X))
    want = np.linalg.solve(K + 0.1 * np.eye(60), np.asarray(y))
    np.testing.assert_allclose(np.asarray(model.A)[:, 0], want, rtol=1e-5, atol=1e-7)
    assert int(model.info["flag"]) == 0


# -- the carry: a checkpoint of the parent's layout resumes -------------------

CARRY = {  # key -> dtype, as the parent (commit 9051ee3) wrote them under x64
    "lsqr": dict(U="float64", V="float64", W="float64", Y="float64", alpha="float64",
                 anorm="float64", arnorm_best="float64", beta="float64",
                 bnorm="float64", done="bool", it="int32", phibar="float64",
                 rhobar="float64", stag="int32"),
    "cg": dict(P="float64", R="float64", X="float64", done="bool", it="int32",
               rz="float64"),
}


def _parent_problem(kind):
    """What ``tests/data/krylov_parent_carry`` was written from: the
    parent's ``ResilientRunner`` (``checkpoint_every=4``), killed after
    its first chunk."""
    rng = np.random.default_rng(2026)
    A, B = rng.standard_normal((80, 10)), rng.standard_normal((80, 3))
    S, C = A.T @ A + np.eye(10), rng.standard_normal((10, 2))
    kp = KrylovParams(iter_lim=40, tolerance=1e-13)
    if kind == "lsqr":
        return lambda: lsqr_chunked(jnp.asarray(A), jnp.asarray(B), params=kp)
    return lambda: cg_chunked(jnp.asarray(S), jnp.asarray(C), params=kp)


@pytest.mark.faults
@pytest.mark.parametrize("kind", CARRY)
def test_a_checkpoint_of_the_parents_carry_resumes(kind, tmp_path):
    make = _parent_problem(kind)
    state = make().init_state()
    assert {k: str(v.dtype) for k, v in state.items()} == CARRY[kind]
    X_ref, info_ref = ResilientRunner(make(), ResilientParams(checkpoint_every=4)).run()
    ck = tmp_path / "ck"
    shutil.copytree(os.path.join(DATA, kind), ck)
    X_res, info_res = ResilientRunner(
        make(), ResilientParams(checkpoint_dir=str(ck), checkpoint_every=4, resume=True)
    ).run()
    assert int(info_res["iterations"]) == int(info_ref["iterations"]) > 4
    # the file was written on another machine: its four iterations are the
    # parent's arithmetic, equal to this tree's to rounding
    np.testing.assert_allclose(np.asarray(X_res), np.asarray(X_ref), rtol=1e-12, atol=1e-14)


@pytest.mark.faults
def test_a_kill_and_resume_through_the_lifted_segment_is_bit_for_bit(tmp_path):
    S, C = spd_of(tall(1, 80, 10)), rhs(2, 10, 2)
    kp = KrylovParams(iter_lim=40, tolerance=1e-13)

    def run(ckdir, plan=None, resume=False):
        return ResilientRunner(
            cg_chunked(S, C, precond=_PlainJacobi(S), params=kp),
            ResilientParams(checkpoint_dir=str(ckdir), checkpoint_every=3, resume=resume),
            fault_plan=plan,
        ).run()

    X_ref, info_ref = run(tmp_path / "ref")
    with pytest.raises(SimulatedPreemption):
        run(tmp_path / "ck", plan=FaultPlan(preempt_after_chunk=1))
    X_res, info_res = run(tmp_path / "ck", resume=True)
    assert np.asarray(X_ref).tobytes() == np.asarray(X_res).tobytes()
    assert int(info_ref["iterations"]) == int(info_res["iterations"])
