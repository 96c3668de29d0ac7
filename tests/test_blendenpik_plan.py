"""Blendenpik's and LSRN's preconditioner stage launches cached programs.

``solvers.accelerated._sketch_once`` applies its sketch through the plan
cache and ``_tri_condest`` is one module-level ``jax.jit``.  Here, at a
shape that takes the route the benchmark's cell takes (the FJLT past its
SRHT-GEMM gate: diagonal, factored WHT, row sample): the planned solve
returns the bytes of the eager one (``SKYLARK_NO_PLANS=1``, the parent's
path) for dense and sparse operands and through the retry loop's
re-sketch and fallback; a second solve from the same context state
traces and lowers nothing; a context with advanced counters costs
exactly one more plan (``ROADMAP.md`` Queue 1 item 5: seeds are literals
of the planned program — the test to flip when they become arguments).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _builds import builds
from jax.experimental import sparse as jsparse

from libskylark_tpu import SketchContext, plans
from libskylark_tpu.sketch import FJLT
from libskylark_tpu.solvers import (
    FasterLeastSquaresParams,
    faster_least_squares,
    lsrn_least_squares,
)

M, N = 4096, 512  # gamma = 4: s = 2048
SOLVERS = {"blendenpik": faster_least_squares, "lsrn": lsrn_least_squares}


@pytest.fixture(scope="module")
def problem():
    rng = np.random.default_rng(30)
    A = jnp.asarray(rng.standard_normal((M, N)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((M,)), jnp.float32)
    mask = jnp.asarray(rng.random((M, N)) < 0.05)
    return A, jsparse.BCOO.fromdense(jnp.where(mask, A, 0)), b


@pytest.fixture(autouse=True)
def _plans_on(monkeypatch):
    monkeypatch.delenv("SKYLARK_NO_PLANS", raising=False)


def solve(solver, A, b, ctx, **params):
    X, info = SOLVERS[solver](A, b, ctx, FasterLeastSquaresParams(**params))
    return np.asarray(X).tobytes(), info


def test_the_shape_takes_the_streamed_wht_route():
    S = FJLT(M, 4 * N, SketchContext(seed=1))
    assert not S._gemm_wins(jnp.float32)


@pytest.mark.parametrize("solver,sketch_type,sparse", [
    ("blendenpik", "FJLT", False),
    ("blendenpik", "JLT", False),
    ("blendenpik", None, True),  # the sparse default: CWT
    ("lsrn", None, False),  # the dense default: JLT
    ("lsrn", "FJLT", False),
    ("lsrn", None, True),
])
def test_planned_and_eager_solves_return_the_same_bytes(
        problem, monkeypatch, solver, sketch_type, sparse):
    A, Asp, b = problem
    A = Asp if sparse else A
    before = plans.stats()
    planned, info = solve(solver, A, b, SketchContext(seed=5), sketch_type=sketch_type)
    after = plans.stats()
    # a BCOO operand takes the eager apply inside plans.apply and says so
    assert after["bypasses"] - before["bypasses"] == (1 if sparse else 0)
    assert (after["hits"] + after["misses"]) - (before["hits"] + before["misses"]) == (
        0 if sparse else 1)
    monkeypatch.setenv("SKYLARK_NO_PLANS", "1")
    eager, info_eager = solve(solver, A, b, SketchContext(seed=5), sketch_type=sketch_type)
    assert plans.stats()["bypasses"] == after["bypasses"] + 1
    assert planned == eager
    assert info["iterations"] == info_eager["iterations"] > 0
    assert info.get("condest") == info_eager.get("condest")
    assert info.get("attempts") == info_eager.get("attempts")
    assert info["recovery"] == info_eager["recovery"]


def test_a_second_solve_builds_nothing_and_a_moved_counter_is_one_more_plan(problem):
    A, _, b = problem
    first, _ = solve("blendenpik", A, b, SketchContext(seed=7))
    before = plans.stats()
    with builds() as seen:
        again, _ = solve("blendenpik", A, b, SketchContext(seed=7))
    after = plans.stats()
    assert seen == []
    assert again == first
    assert (after["hits"], after["misses"], after["traces"]) == (
        before["hits"] + 1, before["misses"], before["traces"])
    # The plan's key is the sketch's JSON, seed and counters included: a
    # caller that keeps one running context compiles one sketch program a
    # solve where the eager apply compiled none.
    running = SketchContext(seed=7)
    solve("blendenpik", A, b, running)
    assert plans.stats()["misses"] == after["misses"]
    moved = running.counter
    assert moved == M + 4 * N  # the diagonal's draws, then the sample's
    solve("blendenpik", A, b, running)
    end = plans.stats()
    assert (end["misses"], end["traces"], end["compiles"]) == (
        after["misses"] + 1, after["traces"] + 1, after["compiles"] + 1)
    # and that state is again one executable for whoever rebuilds it
    with builds() as seen:
        solve("blendenpik", A, b, SketchContext(seed=7, counter=moved))
    assert seen == []
    assert plans.stats()["misses"] == end["misses"]


def test_the_retry_loop_resketches_and_falls_back_as_the_eager_one(problem, monkeypatch):
    """A threshold no sketch can meet: two sketches (s, then 2s, each a plan
    of its own), then the exact SVD solve, with the eager path's numbers."""
    A, _, b = problem
    forced = dict(cond_threshold=1e-3, max_attempts=2)
    before = plans.stats()
    planned, info = solve("blendenpik", A, b, SketchContext(seed=5), **forced)
    after = plans.stats()
    assert (after["hits"] + after["misses"]) - (before["hits"] + before["misses"]) == 2
    monkeypatch.setenv("SKYLARK_NO_PLANS", "1")
    eager, info_eager = solve("blendenpik", A, b, SketchContext(seed=5), **forced)
    assert planned == eager
    assert info == info_eager
    assert (info["attempts"], info["fallback"], info["iterations"]) == (2, "svd", 0)
    attempts = info["recovery"]["attempts"]
    assert [(a["action"], a["verdict"]) for a in attempts] == [
        ("initial", "RESKETCH"), ("grow", "RESKETCH"), ("fallback", "FALLBACK")]
    assert [a["sketch_size"] for a in attempts[:2]] == [4 * N, 8 * N]
    assert info["recovery"]["recovered"] is True


def test_the_condition_estimate_is_one_cached_program():
    from libskylark_tpu.solvers.accelerated import _tri_condest

    rng = np.random.default_rng(31)
    R = jnp.triu(jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)) + 8 * jnp.eye(
        64, dtype=jnp.float32)
    want = np.linalg.norm(np.asarray(R, np.float64), 1) * np.linalg.norm(
        np.linalg.inv(np.asarray(R, np.float64)), 1)
    assert float(_tri_condest(R)) == pytest.approx(want, rel=1e-4)
    other = 2 * R
    with builds() as seen:
        out = _tri_condest(other)
    assert seen == [] and isinstance(out, jax.Array) and out.shape == ()
