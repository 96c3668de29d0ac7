"""The streamed KRR trainer's chunk programs are built once a process.

``ml.krr.gram``, ``zr`` and ``apply_delta`` are module-level ``jax.jit``
programs keyed by a static spec (the maps by value, the chunk, the panel
grid, the feature dtype, ``block_fn``'s identity) and the operands'
shapes; λ is an operand.  Here: a warm call with a fresh kernel, context
and maps of equal value traces and lowers nothing and returns the cold
call's bits; a new seed, λ, s and feature dtype are exactly three, zero,
three and three more cache entries; five chunks build fifteen programs
once; a ``block_fn`` that closes over an array gets a private set, trains
to the same bits and leaves no array behind; one that reads a global the
caller rebinds between calls trains on the new rows; the model is
``large_scale_kernel_ridge``'s.
"""

import functools
import gc
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _builds import builds

from libskylark_tpu import SketchContext
from libskylark_tpu.ml import (
    GaussianKernel,
    KrrParams,
    krr,
    large_scale_kernel_ridge,
    streaming_kernel_ridge,
)

N, D, S, T, PANEL = 384, 8, 24, 2, 128  # shapes no other test file trains at
PROGRAMS = (krr.gram, krr.zr, krr.apply_delta)


def rows_of(start, rows, X):
    """A ``block_fn`` that lives with its module: data through ``block_args``."""
    return jax.lax.dynamic_slice_in_dim(X, start, rows, 0)


def data(seed=5, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((N, D))
    Y = np.tanh(X @ rng.standard_normal((D, T)))
    return jnp.asarray(X, dtype), jnp.asarray(Y, jnp.float32)


def train(X, Y, *, seed=3, lam=0.5, s=S, dtype=jnp.float32, max_split=2 * S,
          block_fn=rows_of, block_args=None):
    """One call as a user makes it: a new kernel, context and maps."""
    model = streaming_kernel_ridge(
        GaussianKernel(D, sigma=3.0), block_fn, (N, D), Y, lam, s,
        SketchContext(seed=seed), KrrParams(max_split=max_split, iter_lim=2),
        block_rows=PANEL, feature_dtype=dtype,
        block_args=(X,) if block_args is None else block_args,
    )
    return np.asarray(model.W)


def built():
    return sum(p._cache_size() for p in PROGRAMS)


@pytest.fixture
def empty_caches():
    for p in PROGRAMS:
        p.clear_cache()
    yield
    for p in PROGRAMS:
        p.clear_cache()


def test_a_warm_call_builds_nothing_and_answers_the_same(empty_caches):
    X, Y = data()
    cold = train(X, Y)
    assert [p._cache_size() for p in PROGRAMS] == [1, 1, 1]
    with builds() as seen:
        warm = train(X, Y)
    assert seen == []  # not a chunk program, not an eager op around them
    assert [p._cache_size() for p in PROGRAMS] == [1, 1, 1]
    assert warm.tobytes() == cold.tobytes()


@pytest.mark.parametrize("change,more", [
    ({"seed": 4}, 3), ({"lam": 0.25}, 0), ({"s": S + 8, "max_split": 4 * S}, 3),
    ({"dtype": jnp.bfloat16}, 3)], ids=["seed", "lam", "s", "feature_dtype"])
def test_what_a_new_value_builds(empty_caches, change, more):
    X, Y = data()
    first = train(X, Y)
    assert built() == 3
    other = train(X, Y, **change)
    assert built() == 3 + more
    assert other.shape[0] == change.get("s", S)
    assert other.tobytes() != first.tobytes()


def test_every_lambda_runs_the_one_executable(empty_caches):
    """λ is an operand: the programs answer for the λ they are given."""
    X, Y = data()
    for lam in (0.5, 0.05):
        got = train(X, Y, lam=lam)
        want = large_scale_kernel_ridge(
            GaussianKernel(D, sigma=3.0), X, Y, lam, S, SketchContext(seed=3),
            KrrParams(max_split=2 * S, iter_lim=2))
        np.testing.assert_allclose(got, np.asarray(want.W), rtol=1e-4, atol=1e-7)
    assert built() == 3


def test_five_chunks_build_fifteen_programs_once(empty_caches):
    X, Y = data()
    cold = train(X, Y, s=6 * D, max_split=0)  # chunks of d, d, d, d, 2d
    assert [p._cache_size() for p in PROGRAMS] == [5, 5, 5]
    with builds() as seen:
        warm = train(X, Y, s=6 * D, max_split=0)
    assert seen == []
    assert built() == 15
    assert warm.tobytes() == cold.tobytes()


def test_a_closure_trains_to_the_same_bits_and_keeps_no_array(empty_caches):
    """A ``block_fn`` that closes over X gets a private set of programs
    that dies with the call: nothing of the process's holds X after it."""
    want = train(*data())

    def closed(seed):
        X, Y = data(seed)
        return train(X, Y, block_args=(), block_fn=lambda start, rows: rows_of(
            start, rows, X))

    assert closed(5).tobytes() == want.tobytes()
    assert built() == 3  # `want`'s: the closure went past the shared set
    gc.collect()
    before = len(jax.live_arrays())
    closed(6)
    gc.collect()
    assert len(jax.live_arrays()) == before
    assert built() == 3


def test_a_rebound_global_is_read_anew(empty_caches, monkeypatch):
    """A top-level ``block_fn`` that reads X from its module's namespace,
    as a script's or a notebook's does: were it traced once a process,
    the second fold would train on the first fold's rows."""
    for seed in (5, 6):
        X, Y = data(seed)
        monkeypatch.setitem(globals(), "_X", X)
        got = train(X, Y, block_fn=_reads_a_global, block_args=())
        assert got.tobytes() == train(X, Y).tobytes()
    assert built() == 3  # `rows_of`'s, the same for both folds


class _Rows:
    def rows(self, start, rows, X):
        return rows_of(start, rows, X)


def _nested():
    def rows(start, rows, X):  # no closure, but gone with its definer
        return rows_of(start, rows, X)

    return rows


_X = None  # what a script binds at its top and rebinds between calls


def _reads_a_global(start, rows, *_):
    return rows_of(start, rows, _X)


def _reads_it_through_a_helper(start, rows, *_):
    return _reads_a_global(start, rows)


def _has_a_default(start, rows, X, axis=0):
    return jax.lax.dynamic_slice_in_dim(X, start, rows, axis)


def _calls_code_alone(start, rows, X):
    return (lambda: rows_of(start, rows, X))()


def _of_a_module_loaded_by_path():
    """As ``benchmarks/run.py`` loads an entry's file: the module is
    executed and kept, and never put into ``sys.modules``."""
    module = types.ModuleType("krr_programs_not_in_sys_modules")
    exec("import jax\n\n\ndef rows(start, rows, X):\n"
         "    return jax.lax.dynamic_slice_in_dim(X, start, rows, 0)\n",
         module.__dict__)
    return module.rows


@pytest.mark.parametrize("block_fn,shared", [
    (rows_of, True),
    (_of_a_module_loaded_by_path(), True),
    (_calls_code_alone, True),
    (_reads_a_global, False),
    (_reads_it_through_a_helper, False),
    (_has_a_default, False),
    (_nested(), False),
    (lambda start, rows, X: rows_of(start, rows, X), False),
    (functools.partial(rows_of), False),
    (_Rows().rows, False),
], ids=["module-level", "loaded-by-path", "calls-code-alone", "reads-a-global",
        "reads-it-through-a-helper", "has-a-default", "nested", "lambda", "partial",
        "bound-method"])
def test_which_block_fn_takes_the_shared_programs(
        block_fn, shared, empty_caches, monkeypatch):
    assert krr._lives_with_its_module(block_fn) is shared
    X, Y = data()
    monkeypatch.setitem(globals(), "_X", X)
    got = train(X, Y, block_fn=block_fn)
    assert built() == (3 if shared else 0)
    assert got.tobytes() == train(X, Y).tobytes()


def test_the_model_is_large_scale_kernel_ridges(empty_caches):
    """The BCD updates of ``large_scale_kernel_ridge`` on the same maps,
    to the tolerance ``tests/test_ml.py`` holds the trainer to."""
    X, Y = data(dtype=jnp.float64)
    params = KrrParams(max_split=D, iter_lim=20, tolerance=1e-6)
    want = large_scale_kernel_ridge(
        GaussianKernel(D, sigma=3.0), X, Y, 0.1, S, SketchContext(seed=11), params)
    got = streaming_kernel_ridge(
        GaussianKernel(D, sigma=3.0), rows_of, (N, D), Y, 0.1, S,
        SketchContext(seed=11), params, block_rows=PANEL, feature_dtype=X.dtype,
        block_args=(X,))
    assert len(got.maps) == len(want.maps) > 1
    np.testing.assert_allclose(
        np.asarray(got.W), np.asarray(want.W), rtol=1e-4, atol=1e-7)
