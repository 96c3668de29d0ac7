"""The program records: what ``utils.profiling.launch`` notes while a
profiler session is open, and what ``records()`` then reads from the
programs lowered again.

Each measured path runs at a tiny size on the CPU.  With no profiler
session nothing is noted; under ``profiling.trace`` every hot program
leaves one record whose module is the name the trace shows it under,
whose ``scopes`` map holds the named scopes the benchmark's metrics read
(``BENCHMARK.json``: ``*_dev_ms`` of kind ``scope_dev_ms``), and whose
signature holds shapes, never arrays.

All in one file: a process has one profiler session at a time, and the
suite gives a file to one worker.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from libskylark_tpu import SketchContext, ml, plans
from libskylark_tpu.linalg import SVDParams, approximate_svd
from libskylark_tpu.solvers import faster_least_squares
from libskylark_tpu.utils import profiling

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import span_reduce  # noqa: E402 - the name a trace shows a program under

pytestmark = pytest.mark.telemetry

ROWS, D, S, T, PANEL = 512, 8, 32, 2, 128
F32 = jnp.float32


def _blendenpik():
    rng = np.random.default_rng(5)
    A = jnp.asarray(rng.standard_normal((2048, 16)), F32)
    return faster_least_squares(A, A @ jnp.ones((16,), F32), SketchContext(seed=11))[0]


def _faster_krr():
    rng = np.random.default_rng(6)
    X = jnp.asarray(rng.standard_normal((256, D)), F32)
    y = np.argmax(np.asarray(X)[:, :3], axis=1)
    return ml.faster_kernel_rlsc(
        ml.GaussianKernel(D, 3.0), X, y, 0.1, S, SketchContext(seed=3),
        ml.KrrParams(tolerance=1e-3)).A


def _block_fn(start, rows, X):
    return jax.lax.dynamic_slice_in_dim(X, start, rows, 0)


def _krr_stream(closure=False):
    rng = np.random.default_rng(7)
    X = jnp.asarray(rng.standard_normal((ROWS, D)), F32)
    Y = jnp.asarray(rng.standard_normal((ROWS, T)), F32)
    # a block_fn that closes over X gets programs of its call's own
    block_fn = (lambda start, rows: _block_fn(start, rows, X)) if closure else _block_fn
    return ml.streaming_kernel_ridge(
        ml.GaussianKernel(D, sigma=3.0), block_fn, (ROWS, D), Y, 1.0, S,
        SketchContext(seed=3), ml.KrrParams(max_split=2 * S, iter_lim=2),
        block_rows=PANEL, feature_dtype=F32,
        block_args=() if closure else (X,)).W


def _svd():
    rng = np.random.default_rng(8)
    A = jnp.asarray(rng.standard_normal((ROWS, 24)), F32)
    return approximate_svd(A, 4, SketchContext(seed=3), SVDParams(num_iterations=2))[1]


def _block_admm():
    rng = np.random.default_rng(9)
    X = jnp.asarray(rng.standard_normal((ROWS, D)), F32)
    y = np.argmax(np.asarray(X)[:, :3], axis=1)
    kernel, ctx = ml.GaussianKernel(D, sigma=3.0), SketchContext(seed=3)
    maps = [kernel.create_rft(S, "regular", ctx) for _ in range(2)]
    return ml.BlockADMMSolver("hinge", "l2", maps, ml.ADMMParams(
        maxiter=3, cache_transforms=False)).train(X, y).W


# path -> (call, {function launched: the scopes its record's map holds})
PATHS = {
    "blendenpik": (_blendenpik, {
        "traced": (), "_tri_condest": (),
        "run": ("krylov.matvec", "krylov.rmatvec", "krylov.precond")}),
    "faster_krr": (_faster_krr, {
        "traced": (), "_woodbury_factor": (),
        "shifted_gram": ("gram.block", "gram.write"),
        "run": ("krylov.matvec", "krylov.precond")}),
    "krr_stream": (_krr_stream, {
        "gram": ("krr.features", "krr.gram_product"),
        "zr": ("krr.features", "krr.zr_product"),
        "apply_delta": ("krr.features", "krr.delta_product")}),
    "svd": (_svd, {
        "_chunk": ("svd.sweep_products", "svd.gram_orth"), "_project": ()}),
    "block_admm": (_block_admm, {
        "admm_factor": ("admm.features", "admm.factor"),
        "admm_iterate": ("admm.features", "admm.thin_products", "admm.prox",
                         "admm.block_solve", "admm.tail")}),
}


@pytest.fixture(autouse=True)
def _no_records_left():
    profiling.reset_records()
    yield
    profiling.reset_records()


def _traced(call, tmp_path):
    call()  # warm: the session below builds nothing
    with profiling.trace(str(tmp_path)):
        call()
    return profiling.records()


def _op_names(rec):
    return {name for entry in rec["scopes"].values()
            for name in [entry[0]] + [op_name for _, op_name in entry[1:]]}


@pytest.mark.parametrize("path", PATHS)
def test_with_no_profiler_session_nothing_is_noted(path):
    PATHS[path][0]()
    assert not profiling.tracing()
    assert profiling.records() == []


@pytest.mark.parametrize("path", PATHS)
def test_under_a_session_every_hot_program_leaves_one_record(path, tmp_path):
    call, want = PATHS[path]
    recs = _traced(call, tmp_path)
    assert sorted(r["module"] for r in recs) == sorted(
        span_reduce.module_of(f) for f in want)
    for function, scopes in want.items():
        (rec,) = [r for r in recs if r["module"] == span_reduce.module_of(function)]
        assert "error" not in rec, rec
        names = "\n".join(_op_names(rec))
        for scope in scopes:
            assert scope in names, (function, scope)
        for field in ("argument_bytes", "output_bytes", "temp_bytes", "alias_bytes"):
            assert isinstance(rec[field], int) and rec[field] >= 0, field
        leaves = jax.tree.leaves(rec["signature"])
        assert any(isinstance(x, jax.ShapeDtypeStruct) for x in leaves)
        assert not any(isinstance(x, (jax.Array, np.ndarray)) for x in leaves)


def test_records_lowers_a_plan_without_counting_a_trace(tmp_path):
    _blendenpik()
    with profiling.trace(str(tmp_path)):
        _blendenpik()
    before = plans.stats()
    (rec,) = [r for r in profiling.records() if r["module"] == "jit_traced"]
    assert rec["scopes"] and plans.stats() == before


@pytest.mark.parametrize("closure", [True, False], ids=["rebuilt", "shared"])
def test_a_second_call_of_a_trainer_adds_no_key(tmp_path, closure):
    """Programs built anew every call leave one set behind, the newest;
    the shared ones are keyed by their spec's value and stay."""
    _krr_stream(closure)
    with profiling.trace(str(tmp_path)):
        _krr_stream(closure)
        first = {id(n.fn) for n in profiling._NOTED.values()}
        _krr_stream(closure)
        second = {id(n.fn) for n in profiling._NOTED.values()}
    assert len(first) == len(second) == 3
    assert not first & second if closure else first == second
    assert len(profiling.records()) == 3


def test_the_65th_key_evicts_the_first():
    f = jax.jit(lambda x: x + 1)
    for n in range(1, profiling.MAX_RECORDS + 2):
        profiling.note(f, (np.zeros((n,), np.float32),), {})
    shapes = [n.args[0].shape for n in profiling._NOTED.values()]
    assert shapes == [(n,) for n in range(2, profiling.MAX_RECORDS + 2)]


def test_a_program_that_cannot_be_lowered_again_gives_an_error_not_an_exception():
    f = jax.jit(lambda x: x + 1)
    profiling.note(f, (np.zeros((3,), np.float32),), {"no_such_argument": 1})
    (rec,) = profiling.records()
    assert "TypeError" in rec["error"] and "scopes" not in rec
    assert rec["module"] == "jit__lambda_"


def test_nothing_is_noted_under_an_enclosing_trace(tmp_path):
    inner = jax.jit(lambda x: x * 2)
    outer = jax.jit(lambda x: profiling.launch(inner, x) + 1)
    with profiling.trace(str(tmp_path)):
        outer(jnp.ones((4,), F32))
    assert profiling.records() == []


def test_a_committed_arrays_sharding_rides_the_signature_an_uncommitted_ones_not():
    f = jax.jit(lambda x, y: x + y)
    dev = jax.devices()[0]
    profiling.note(f, (jax.device_put(jnp.ones((4,), F32), dev), jnp.ones((4,), F32)), {})
    (noted,) = profiling._NOTED.values()
    assert noted.args[0].sharding is not None and noted.args[1].sharding is None
    assert "error" not in profiling.records()[0]


HLO = """HloModule jit_f, is_scheduled=true

%fused_computation.1 (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0:T(8,128)} parameter(0)
  ROOT %convolution.2 = f32[8,8]{1,0:T(8,128)} convolution(%p0, %p0), dim_labels=bf_io->bf, metadata={op_name="jit(f)/while/body/a.product/dot_general" stack_frame_id=3}
}

%fused_computation.2 (p1: f32[8,8]) -> f32[8,8] {
  %p1 = f32[8,8]{1,0:T(8,128)} parameter(0)
  %fusion.9 = f32[8,8]{1,0:T(8,128)} fusion(%p1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(f)/while/body/a.product/dot_general"}
  ROOT %add.3 = f32[8,8]{1,0:T(8,128)} add(%fusion.9, %p1), metadata={op_name="jit(f)/while/body/a.tail/add"}
}

%body.4 (arg: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %arg = (s32[]{:T(128)}, f32[8,8]{1,0:T(8,128)}) parameter(0)
  %gte.1 = f32[8,8]{1,0:T(8,128)} get-tuple-element(%arg), index=1
  %add_fusion.7 = f32[8,8]{1,0:T(8,128)} fusion(%gte.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(f)/while/body/a.tail/add" stack_frame_id=5}
  ROOT %tuple.2 = (s32[]{:T(128)}, f32[8,8]{1,0:T(8,128)}) tuple(%gte.1, %add_fusion.7)
}

ENTRY %main.5 (x: f32[8,8]) -> f32[8,8] {
  %x = f32[8,8]{1,0:T(8,128)} parameter(0), metadata={op_name="x"}
  %while.3 = (s32[]{:T(128)}, f32[8,8]{1,0:T(8,128)}) while(%x), condition=%cond.6, body=%body.4, metadata={op_name="jit(f)/while"}
  ROOT %copy.1 = f32[8,8]{1,0:T(8,128)} copy(%x)
}
"""


@pytest.mark.parametrize("instruction,want", [
    ("while.3", ["jit(f)/while"]),
    ("copy.1", [""]),
    ("tuple.2", [""]),
    ("add_fusion.7", [
        "jit(f)/while/body/a.tail/add",
        ["parameter", ""],
        ["fusion", "jit(f)/while/body/a.product/dot_general"],
        ["parameter", ""],
        ["convolution", "jit(f)/while/body/a.product/dot_general"],
        ["add", "jit(f)/while/body/a.tail/add"]]),
], ids=lambda v: v if isinstance(v, str) else "")
def test_the_map_read_from_a_compiled_programs_text(instruction, want):
    scopes = profiling.hlo_scopes(HLO)
    assert scopes[instruction] == want
    # a fusion's callee has no entries of its own: no event is named by them
    assert "convolution.2" not in scopes and "fusion.9" not in scopes


def test_an_executable_cached_from_a_source_with_other_scopes_is_compiled_again(tmp_path):
    """The compile cache's key leaves metadata out: the same operations
    under a renamed scope come back from it with the old ``op_name``s,
    and ``records()`` has to say the scopes of the source that ran."""
    from jax.experimental.compilation_cache import compilation_cache

    was = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    try:
        def program(scope):
            def f(x):
                with jax.named_scope(scope):
                    return jnp.sin(x @ x.T) + 1
            return jax.jit(f)

        x = np.ones((64, 64), np.float32)
        program("old.scope")(x).block_until_ready()
        new = program("new.scope")
        new(x).block_until_ready()  # the cache's executable: the old names
        assert profiling._stale(new.lower(x).compile().as_text(), {"new.scope"})
        profiling.note(new, (x,), {})
        names = "\n".join(_op_names(profiling.records()[0]))
        assert "new.scope" in names and "old.scope" not in names
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
