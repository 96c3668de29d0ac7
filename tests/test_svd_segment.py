"""The randomized SVD's sweep segment is built once a shape and never holds A.

``linalg.svd._chunk`` is one module-level ``jax.jit``: its arguments are
the carry ``{"it", "Y"}``, the operand A (dense or BCOO) and the budget as
two scalars, its one static whether the sweeps orthonormalize.  Here: a
second ``approximate_svd`` at the same shapes traces and lowers nothing
and answers with the same bytes; the segment's answer is, bit for bit,
that of the per-call closure it replaced; a new shape or dtype adds
exactly one entry to ``_chunk``'s cache and every chunk length and
``num_iterations`` share one; a second matrix of the same shape is
factored from its own entries; the lowered program holds no literal of
A's size, carries the name the benchmark's reader looks for, and nothing
that outlives a call holds a device array; an operand sharded by rows
over a 2x2 mesh is served from the cache too.
"""

import gc
import json
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.experimental import sparse as jsparse
from _builds import BUILD_EVENTS, builds  # the traces and lowerings of a block

from libskylark_tpu import SketchContext
from libskylark_tpu.linalg import SVDParams, approximate_svd, approximate_svd_chunked, svd
from libskylark_tpu.parallel import default_mesh, shard_rows_padded
from libskylark_tpu.resilient import ResilientParams, ResilientRunner

M, N, K = 211, 29, 5  # shapes no other test file factors at
LOWER = BUILD_EVENTS[1]  # a lowering; the first is a trace
READER = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                      "benchmarks", "layer_metrics", "svd_power_dev_ms.json")


def low_rank(seed, m=M, n=N, dtype=jnp.float64):
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((m, K)) @ rng.standard_normal((K, n))
    return jnp.asarray(A + 0.01 * rng.standard_normal((m, n)), dtype)


def sparse(seed, m=M, n=N, dtype=jnp.float64):
    keep = np.random.default_rng(m * n).random((m, n)) < 0.3  # nse is part of the shape
    return jsparse.BCOO.fromdense(low_rank(seed, m, n, dtype) * keep)


def on_the_mesh(seed, m=M, n=N, dtype=jnp.float32):
    """Rows over a 2x2 mesh of four of the suite's virtual devices, as
    ``tests/benchmark/_svd_mesh_child.py`` lays the cell's operand."""
    return shard_rows_padded(low_rank(seed, m, n, dtype), default_mesh(4))[0]


OPERANDS = {"dense": low_rank, "bcoo": sparse, "sharded": on_the_mesh}


def factor(A, q=2, skip_qr=False, rank=K):
    U, s, V = approximate_svd(A, rank, SketchContext(seed=17),
                              SVDParams(num_iterations=q, skip_qr=skip_qr))
    return np.asarray(U), np.asarray(s), np.asarray(V)


def same_bytes(got, want):
    return all(g.tobytes() == w.tobytes() for g, w in zip(got, want, strict=True))


@pytest.fixture
def empty_cache():
    svd._chunk.clear_cache()
    yield
    svd._chunk.clear_cache()


@pytest.mark.parametrize("q", [0, 1, 2])
@pytest.mark.parametrize("skip_qr", [False, True])
@pytest.mark.parametrize("operand", OPERANDS)
def test_a_warm_factorization_builds_nothing_and_answers_the_same(
        operand, skip_qr, q, empty_cache):
    A = OPERANDS[operand](1)
    cold = factor(A, q, skip_qr)
    assert svd._chunk._cache_size() == 1
    with builds() as seen:
        warm = factor(A, q, skip_qr)
    if operand == "bcoo":  # BCOO's eager products re-trace closures of their own
        assert LOWER not in seen
    else:
        assert seen == []  # not the segment, not an eager op around it
    assert svd._chunk._cache_size() == 1
    assert same_bytes(warm, cold)


# -- the arithmetic is the parent's -------------------------------------------


def parent_segment(A, niter, orthogonalize):
    """The segment as the parent (commit 1c14356) built it in every
    ``approximate_svd_chunked``: a ``jax.jit`` closed over the sweep limit
    and the flag, the chunk's length a static."""

    @partial(jax.jit, static_argnames=("num_iters",))
    def _chunk(st, A, num_iters: int):
        stop = jnp.minimum(st["it"] + num_iters, niter)

        def cond(c):
            return c["it"] < stop

        def body(c):
            Y = A @ (A.T @ c["Y"])
            return dict(it=c["it"] + 1, Y=svd.gram_orth(Y) if orthogonalize else Y)

        return lax.while_loop(cond, body, st)

    return lambda st, num_iters: _chunk(st, A, num_iters)


@pytest.mark.parametrize("q", [0, 1, 2, 5])
@pytest.mark.parametrize("skip_qr", [False, True])
@pytest.mark.parametrize("operand", ["dense", "dense-f32", "bcoo", "sharded"])
def test_the_segment_answers_bit_for_bit_as_the_per_call_closure_did(operand, skip_qr, q):
    make, _, dtype = operand.partition("-")
    A = OPERANDS[make](2, dtype=jnp.float32) if dtype else OPERANDS[make](2)
    sol = approximate_svd_chunked(A, K, SketchContext(seed=17),
                                  SVDParams(num_iterations=q, skip_qr=skip_qr))
    old = parent_segment(A, q, not skip_qr)
    got = want = sol.init_state()  # one sketch: a context's second draw is another
    for length in (1, 3, 2):  # a chunk, one cut short by the limit, one past it
        got, want = sol.step_chunk(got, length), old(want, length)
        assert int(got["it"]) == int(want["it"])
        assert got["Y"].dtype == want["Y"].dtype and got["it"].dtype == jnp.int32
        assert np.asarray(got["Y"]).tobytes() == np.asarray(want["Y"]).tobytes()
    assert int(got["it"]) == q and sol.is_done(got)


# -- one executable a shape ---------------------------------------------------


@pytest.mark.parametrize("operand", OPERANDS)
def test_a_new_shape_or_dtype_adds_exactly_one(operand, empty_cache):
    make = OPERANDS[operand]
    other = jnp.float64 if operand == "sharded" else jnp.float32
    factor(make(1))
    assert svd._chunk._cache_size() == 1
    factor(make(1, M + 8, N + 3))
    assert svd._chunk._cache_size() == 2
    factor(make(1, dtype=other))
    assert svd._chunk._cache_size() == 3
    factor(make(3))  # other values, the shapes of the first
    assert svd._chunk._cache_size() == 3
    factor(make(1), rank=K + 2)  # a wider sketch is a new shape of Y
    assert svd._chunk._cache_size() == 4


@pytest.mark.parametrize("operand", OPERANDS)
def test_every_budget_runs_the_one_executable(operand, empty_cache):
    """The chunk length and the sweep limit are arguments: a runner's
    short last chunk and another ``num_iterations`` dispatch the program
    the first chunk built."""
    A = OPERANDS[operand](1)
    one = factor(A, 5)
    assert svd._chunk._cache_size() == 1
    U, s, V = ResilientRunner(
        approximate_svd_chunked(A, K, SketchContext(seed=17), SVDParams(num_iterations=5)),
        ResilientParams(checkpoint_every=2),
    ).run()
    assert same_bytes((np.asarray(U), np.asarray(s), np.asarray(V)), one)
    # on a mesh the first chunk's counter comes from ``init_state``, on one
    # device, and every later chunk's from the segment, laid over the mesh:
    # another layout of the arguments, built once as well
    built = 2 if operand == "sharded" else 1
    assert svd._chunk._cache_size() == built
    for q in (0, 1, 3, 8):
        factor(A, q)
    ResilientRunner(
        approximate_svd_chunked(A, K, SketchContext(seed=17), SVDParams(num_iterations=7)),
        ResilientParams(checkpoint_every=3),
    ).run()
    assert svd._chunk._cache_size() == built
    factor(A, 1, skip_qr=True)  # the one static: sweeps without the Gram passes
    assert svd._chunk._cache_size() == built + 1


@pytest.mark.parametrize("operand", OPERANDS)
def test_the_second_matrix_is_factored_from_its_own_entries(operand, empty_cache):
    """Same shapes, other A: a cached segment that kept the first call's
    operand would give the first matrix's factors again."""
    make = OPERANDS[operand]
    first, second = factor(make(1)), factor(make(5))
    assert svd._chunk._cache_size() == 1
    svd._chunk.clear_cache()
    assert same_bytes(second, factor(make(5)))  # as a process that never saw the first
    assert not same_bytes(second, first)
    A = make(5)
    dense = np.asarray(A.todense() if hasattr(A, "todense") else A, np.float64)
    sigma = np.linalg.svd(dense, compute_uv=False)
    np.testing.assert_allclose(second[1][0], sigma[0], rtol=1e-3)
    U, s, V = second
    resid = np.linalg.norm((U[:dense.shape[0]] * s) @ V.T - dense)
    assert resid < 1.2 * np.linalg.norm(sigma[K:]) + 1e-4 * sigma[0]


# -- the program: A a parameter, the name the benchmark reads -------------------


def lowered(A, orthogonalize=True):
    sol = approximate_svd_chunked(A, K, SketchContext(seed=17), SVDParams(num_iterations=2))
    return svd._chunk.lower(sol.init_state(), A, 2, 2, orthogonalize=orthogonalize)


@pytest.mark.parametrize("orthogonalize", [True, False])
def test_the_segment_takes_a_as_an_argument_not_a_literal(orthogonalize):
    m, n = 1024, 64  # A is 256 KiB of f32: a literal of it is > 512 KB of text
    text = lowered(low_rank(1, m, n, jnp.float32), orthogonalize).as_text()
    assert len(text) < 60_000
    for line in text.splitlines():
        if "constant" in line:
            assert f"tensor<{m}x{n}x" not in line and f"tensor<{m}x{2 * K}x" not in line
    assert f"tensor<{m}x{n}xf32>" in text  # A is there, as a parameter


def test_the_budget_is_two_scalar_parameters():
    args = lowered(low_rank(1, dtype=jnp.float32)).args_info[0]
    st, A, num_iters, niter = args
    assert sorted(st) == ["Y", "it"]
    for scalar in (st["it"], num_iters, niter):
        assert scalar.shape == () and jnp.issubdtype(scalar.dtype, jnp.integer)
    assert A.shape == (M, N) and st["Y"].shape == (M, 2 * K)


@pytest.mark.parametrize("operand", OPERANDS)
def test_the_device_module_has_the_name_the_benchmark_reads(operand):
    """``svd_power_dev_ms`` and ``svd_power_roofline`` find the sweeps in a
    device trace by the module's name: the profiler writes
    ``jit_<function>(<fingerprint>)`` and ``trace_reduce`` strips the
    fingerprint."""
    with open(READER) as f:
        pattern = json.load(f)["reader"]["module"]
    text = lowered(OPERANDS[operand](1, dtype=jnp.float32)).as_text()
    name = re.search(r"module @(\w+)", text).group(1)
    assert re.search(pattern, name), (pattern, name)
    assert not re.search(pattern, "jit_run") and not re.search(pattern, "jit__project")


# -- nothing is kept ----------------------------------------------------------


@pytest.mark.parametrize("operand", OPERANDS)
def test_nothing_that_outlives_a_call_holds_a_device_array(operand):
    def call(seed):
        A = OPERANDS[operand](seed, 256, 16)
        U, s, V = approximate_svd(A, K, SketchContext(seed=17), SVDParams(num_iterations=2))
        return float(jnp.sum(s)) + float(U[0, 0]) + float(V[0, 0])

    call(1)  # whatever JAX caches for the process is cached now
    gc.collect()
    before = len(jax.live_arrays())
    call(3)
    gc.collect()
    assert len(jax.live_arrays()) == before


def test_the_solver_holds_no_program_of_its_own():
    """``step_chunk`` closes over A and two Python values; the program
    belongs to the module, so dropping the solver drops no executable and
    keeping the module keeps no operand."""
    sol = approximate_svd_chunked(low_rank(1), K, SketchContext(seed=17), SVDParams())
    cells = [c.cell_contents for c in sol.step_chunk.__closure__]
    assert not any(hasattr(c, "lower") for c in cells)  # no jax.jit in there
    assert sum(isinstance(c, jax.Array) for c in cells) == 1  # A, dropped with the solver
