"""The program's stage spans, read back from a profiler trace on the CPU.

``telemetry.span`` opens the annotation ``skylark:<name>`` whether or not
``SKYLARK_TELEMETRY`` is set, so a ``jax.profiler`` trace around a solve
shows every stage on the device trace's clock.  Here: each measured path
runs once under ``jax.profiler.start_trace`` with telemetry unset; every
span of the path is there the right number of times, the stage spans lie
inside their entry span, and the answer is bit-identical to the one
computed with no profiler session.  With telemetry on, the ledger's
``span_end`` says what JAX built inside the span.

All in one file: a process has one profiler session at a time, and the
suite gives a file to one worker.
"""

import atexit
import collections
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring

from libskylark_tpu import SketchContext, ml, telemetry
from libskylark_tpu.linalg import approximate_least_squares
from libskylark_tpu.solvers import faster_least_squares, lsqr, lsrn_least_squares

pytestmark = pytest.mark.telemetry

M, N = 2048, 16
ROWS, D, S, T, PANEL = 512, 8, 32, 2, 128


def _ls_problem():
    rng = np.random.default_rng(5)
    A = jnp.asarray(rng.standard_normal((M, N)), jnp.float32)
    return A, A @ jnp.ones((N,), jnp.float32)


def _blendenpik():
    A, b = _ls_problem()
    return faster_least_squares(A, b, SketchContext(seed=11))[0]


def _lsrn():
    A, b = _ls_problem()
    return lsrn_least_squares(A, b, SketchContext(seed=11))[0]


def _lsqr_callables():
    A, b = _ls_problem()
    return lsqr(((lambda x: A @ x), (lambda y: A.T @ y)), b)[0]


def _sketch_solve():
    A, b = _ls_problem()
    return approximate_least_squares(A, b, SketchContext(seed=11))


def _block_fn(start, rows, X):
    return jax.lax.dynamic_slice_in_dim(X, start, rows, 0)


def _krr_train():
    rng = np.random.default_rng(7)
    X = jnp.asarray(rng.standard_normal((ROWS, D)), jnp.float32)
    Y = jnp.asarray(rng.standard_normal((ROWS, T)), jnp.float32)
    model = ml.streaming_kernel_ridge(
        ml.GaussianKernel(D, sigma=3.0), _block_fn, (ROWS, D), Y, 1.0, S,
        SketchContext(seed=3), ml.KrrParams(max_split=2 * S, iter_lim=2),
        block_rows=PANEL, feature_dtype=jnp.float32, block_args=(X,),
    )
    return model.W


def _block_admm(cache_transforms=False):
    rng = np.random.default_rng(9)
    X = jnp.asarray(rng.standard_normal((ROWS, D)), jnp.float32)
    y = np.argmax(np.asarray(X)[:, :3], axis=1)
    kernel, ctx = ml.GaussianKernel(D, sigma=3.0), SketchContext(seed=3)
    maps = [kernel.create_rft(S, "regular", ctx) for _ in range(2)]
    return ml.BlockADMMSolver("hinge", "l2", maps, ml.ADMMParams(
        maxiter=3, cache_transforms=cache_transforms)).train(X, y).W


# path -> (call, entry span or None, {stage span: times a call})
PATHS = {
    # the sketch is the planned apply: its two spans open under `.sketch`
    "blendenpik": (_blendenpik, "blendenpik", {
        "blendenpik.sketch": 1, "plans.lookup": 1, "sketch.apply": 1,
        "blendenpik.factor": 1, "blendenpik.condest": 1,
        "krylov.init": 1, "krylov.segment": 1, "krylov.result": 1,
        "guard.check": 1}),
    "lsrn": (_lsrn, "lsrn", {
        "lsrn.sketch": 1, "plans.lookup": 1, "sketch.apply": 1,
        "lsrn.factor": 1, "krylov.init": 1,
        "krylov.segment": 1, "krylov.result": 1, "guard.check": 2}),
    # a (matvec, rmatvec) pair is opaque to the stepper: the lifted segment
    "lsqr_callables": (_lsqr_callables, None, {
        "krylov.init": 1, "krylov.lift": 1, "krylov.segment": 1,
        "krylov.result": 1}),
    # A and b are sketched by one plan each; the guard certifies attempt 0
    "sketch_solve": (_sketch_solve, "sketch_solve", {
        "plans.lookup": 2, "sketch.apply": 2, "guard.certify": 1,
        "sketch_solve.small": 1, "guard.check": 1}),
    # one feature chunk, two sweeps: the Gram and its factor in sweep 0 only
    "krr_train": (_krr_train, "krr_train", {
        "krr.programs": 1, "krr.gram": 1, "krr.factor": 1, "krr.zr": 2,
        "krr.solve": 2, "krr.apply_delta": 2, "krr.converge": 4}),
    # the stages of one BlockADMM call, blocks remade: the factor program
    # and the scan of all iterations are one launch each
    "block_admm_train": (_block_admm, "block_admm_train", {
        "admm.labels": 1, "admm.transform": 1, "admm.factor": 1,
        "admm.iterate": 1, "admm.result": 1}),
}
PHASES = {"krr_train": {"sweep0": 1, "sweep": 1}}  # PhaseTimer's, no dot: no stage

# the CPU client's event around one program's execution (on the chip the
# benchmark's readers match ``PJRT_LoadedExecutable_Execute``)
LAUNCH = "PjRtCpuExecutable::Execute"

_TRACED: dict = {}


def _events_of(trace_dir, keep):
    """``(name, start, end)`` of every event of the trace that ``keep(name)``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return [
        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for ev in line.events
        if keep(ev.name)]


def _spans_of(trace_dir):
    """``(name, start, end)`` of every ``skylark:`` event of the trace."""
    return [(name[len("skylark:"):], start, end) for name, start, end
            in _events_of(trace_dir, lambda name: name.startswith("skylark:"))]


def under_profiler(call, trace_dir):
    """``call()`` under a profiler session (the Python tracer off: the
    spans are ``TraceMe`` events) and the spans the trace then holds."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        out = call()
    finally:
        jax.profiler.stop_trace()
    return out, _spans_of(str(trace_dir))


def traced(path, tmp_path_factory):
    """One warm call with no profiler, then one under a session."""
    if path not in _TRACED:
        call = PATHS[path][0]
        plain = np.asarray(call())
        under, spans = under_profiler(
            call, tmp_path_factory.mktemp("trace_" + path))
        _TRACED[path] = (plain, np.asarray(under), spans)
    return _TRACED[path]


@pytest.fixture(autouse=True)
def _telemetry_unset(monkeypatch):
    monkeypatch.delenv("SKYLARK_TELEMETRY", raising=False)
    monkeypatch.delenv("SKYLARK_TELEMETRY_DIR", raising=False)


@pytest.mark.parametrize("path", PATHS)
def test_every_span_is_in_the_trace_once_a_call(path, tmp_path_factory):
    _, entry, stages = PATHS[path]
    got = collections.Counter(name for name, _, _ in traced(path, tmp_path_factory)[2])
    entries = {entry: 1} if entry else {}
    assert got == {**entries, **stages, **PHASES.get(path, {})}


@pytest.mark.parametrize("path", [p for p in PATHS if PATHS[p][1]])
def test_stage_spans_lie_inside_their_entry_span(path, tmp_path_factory):
    entry = PATHS[path][1]
    spans = traced(path, tmp_path_factory)[2]
    (lo, hi), = [(s, e) for name, s, e in spans if name == entry]
    for name, s, e in spans:
        assert lo <= s and e <= hi, name
        assert ("." in name) == (name in PATHS[path][2]), name


@pytest.mark.parametrize("path", PATHS)
def test_the_answer_under_a_profiler_session_is_bit_identical(path, tmp_path_factory):
    plain, under, _ = traced(path, tmp_path_factory)
    assert plain.tobytes() == under.tobytes()


def test_each_blendenpik_stage_launches_one_program(tmp_path_factory):
    """Sketch, QR and condition estimate are a cached program each, every
    one launched under its own stage span (the benchmark reads device time
    and idle by these spans: a stage fused into its neighbour's program
    would silence its metric)."""
    _blendenpik()
    trace_dir = tmp_path_factory.mktemp("trace_launches")
    _, spans = under_profiler(_blendenpik, trace_dir)
    launches = [s for _, s, _ in _events_of(str(trace_dir), LAUNCH.__eq__)]
    for stage in ("blendenpik.sketch", "blendenpik.factor", "blendenpik.condest"):
        (lo, hi), = [(s, e) for name, s, e in spans if name == stage]
        assert sum(lo <= t <= hi for t in launches) == 1, stage


def test_a_second_attempt_opens_the_attempt_spans_again(tmp_path_factory):
    """Per attempt: a threshold no sketch can meet makes Blendenpik
    re-sketch ``max_attempts`` times and fall back."""
    from libskylark_tpu.solvers import FasterLeastSquaresParams

    A, b = _ls_problem()
    (_, info), spans = under_profiler(
        lambda: faster_least_squares(
            A, b, SketchContext(seed=11),
            FasterLeastSquaresParams(max_attempts=2, cond_threshold=1.0)),
        tmp_path_factory.mktemp("trace_attempts"))
    assert info["fallback"] == "svd"
    got = collections.Counter(name for name, _, _ in spans)
    assert got == {"blendenpik": 1, "blendenpik.sketch": 2, "plans.lookup": 2,
                   "sketch.apply": 2, "blendenpik.factor": 2,
                   "blendenpik.condest": 2, "blendenpik.fallback": 1}


def test_with_telemetry_unset_a_solve_leaves_nothing_behind():
    """No ledger, no listener, no ``atexit`` hook, no registry write."""
    telemetry.reset()
    before = (len(monitoring.get_event_duration_listeners()),
              len(monitoring.get_event_listeners()), atexit._ncallbacks())
    _blendenpik()
    assert before == (len(monitoring.get_event_duration_listeners()),
                      len(monitoring.get_event_listeners()), atexit._ncallbacks())
    assert telemetry.ledger_path() is None
    assert telemetry.snapshot()["counters"] == {}


def test_with_telemetry_on_span_end_says_what_jax_built_inside(tmp_path, monkeypatch):
    """The LSQR segment is built by the first solve at a shape and
    dispatched from ``jax.jit``'s cache by every later one: the ledger
    says so without a profiler.  ``snapshot()`` sums it by span name, so
    the segment's hit share is 1 - lowerings / calls."""
    from libskylark_tpu.solvers import krylov

    off = _blendenpik()
    krylov.run.clear_cache()  # the first solve below is the cold one
    monkeypatch.setenv("SKYLARK_TELEMETRY", "1")
    telemetry.configure(str(tmp_path))
    telemetry.reset()
    try:
        on = [_blendenpik(), _blendenpik()]
        telemetry.flush()
        with open(telemetry.ledger_path()) as fh:
            events = [json.loads(line) for line in fh]
        snap = telemetry.snapshot()
    finally:
        telemetry.close()
        telemetry.configure(None)
        telemetry.reset()
    assert all(np.asarray(x).tobytes() == np.asarray(off).tobytes() for x in on)
    assert not any(e["name"] == "krylov.lift" for e in events)
    ends = [e["attrs"] for e in events
            if e["kind"] == "span_end" and e["name"] == "krylov.segment"]
    assert len(ends) == 2
    cold, warm = ends
    assert cold["lowerings"] == 1 and cold["lower_s"] > 0
    assert cold["traces"] >= 1 and cold["trace_s"] > 0
    assert not {"lowerings", "traces", "compiles"} & set(warm)
    # the entry span holds its stages' builds
    entries = [e["attrs"] for e in events
               if e["kind"] == "span_end" and e["name"] == "blendenpik"]
    assert entries[0]["lowerings"] >= cold["lowerings"]
    assert "lowerings" not in entries[1]
    starts = {e["seq"]: e for e in events if e["kind"] == "span_start"}
    seg = [e for e in events
           if e["kind"] == "span_start" and e["name"] == "krylov.segment"][1]
    assert starts[seg["attrs"]["parent"]]["name"] == "blendenpik"
    per_name = snap["spans"]["krylov.segment"]
    assert per_name["calls"] == 2 and per_name["lowerings"] == 1
    assert per_name["lower_s"] == pytest.approx(cold["lower_s"], abs=1e-5)
    assert "lowerings" not in snap["spans"].get("guard.check", {})


@pytest.mark.parametrize("cache", [True, False], ids=["cached", "remade"])
def test_a_second_block_admm_call_builds_no_program(tmp_path, monkeypatch, cache):
    """Transform, factor and the iteration scan are module-level programs
    keyed by shapes and the serialized maps: a second ``train`` at the
    same shapes -- a new solver, new map objects -- traces, lowers and
    compiles nothing, on either route."""
    off = _block_admm(cache)
    monkeypatch.setenv("SKYLARK_TELEMETRY", "1")
    telemetry.configure(str(tmp_path))
    telemetry.reset()
    try:
        on = _block_admm(cache)
        telemetry.flush()
        with open(telemetry.ledger_path()) as fh:
            events = [json.loads(line) for line in fh]
    finally:
        telemetry.close()
        telemetry.configure(None)
        telemetry.reset()
    assert np.asarray(on).tobytes() == np.asarray(off).tobytes()
    ends = {e["name"]: e["attrs"] for e in events if e["kind"] == "span_end"}
    assert set(ends) == {"block_admm_train"}  # the stages are the timer's phases
    assert not {"lowerings", "traces", "compiles"} & set(ends["block_admm_train"])


def test_a_second_krr_call_builds_no_chunk_program(tmp_path, monkeypatch):
    """The streamed trainer's three chunk programs are module-level and
    keyed by the maps' value: the cold call builds each once, under the
    span that launches it, and a warm call's ``span_end``s carry no
    build.  ``snapshot()`` sums them by span name, so the Gram program's
    hit share is 1 - lowerings / calls."""
    from libskylark_tpu.ml import krr

    off = _krr_train()
    for program in (krr.gram, krr.zr, krr.apply_delta):
        program.clear_cache()  # the first call below is the cold one
    monkeypatch.setenv("SKYLARK_TELEMETRY", "1")
    telemetry.configure(str(tmp_path))
    telemetry.reset()
    try:
        on = [_krr_train(), _krr_train()]
        telemetry.flush()
        with open(telemetry.ledger_path()) as fh:
            events = [json.loads(line) for line in fh]
        snap = telemetry.snapshot()
    finally:
        telemetry.close()
        telemetry.configure(None)
        telemetry.reset()
    assert all(np.asarray(x).tobytes() == np.asarray(off).tobytes() for x in on)
    # (`traces` also counts dispatches off `jax.jit`'s fast path)
    built = {"lowerings", "compiles"}
    for name, times in (("krr.gram", 1), ("krr.zr", 2), ("krr.apply_delta", 2)):
        ends = [e["attrs"] for e in events
                if e["kind"] == "span_end" and e["name"] == name]
        assert len(ends) == 2 * times
        assert ends[0]["lowerings"] == 1 and ends[0]["lower_s"] > 0
        assert not any(built & set(end) for end in ends[1:]), name
        per_name = snap["spans"][name]
        assert per_name["calls"] == 2 * times and per_name["lowerings"] == 1
    cold, warm = [e["attrs"] for e in events
                  if e["kind"] == "span_end" and e["name"] == "krr_train"]
    assert cold["lowerings"] >= 3 and not built & set(warm)


def test_each_block_admm_stage_launches_one_program(tmp_path_factory):
    """The benchmark reads the factor and iterate programs' device time
    by the spans they are launched under."""
    _block_admm()
    trace_dir = tmp_path_factory.mktemp("trace_admm_launches")
    _, spans = under_profiler(_block_admm, trace_dir)
    launches = [s for _, s, _ in _events_of(str(trace_dir), LAUNCH.__eq__)]
    for stage in ("admm.factor", "admm.iterate"):
        (lo, hi), = [(s, e) for name, s, e in spans if name == stage]
        assert sum(lo <= t <= hi for t in launches) == 1, stage


def test_the_one_place_that_builds_an_annotation():
    """Spans and ``PhaseTimer`` both go through ``utils.profiling``; no
    other module of the library constructs a ``TraceAnnotation``."""
    import libskylark_tpu

    root = os.path.dirname(libskylark_tpu.__file__)
    holders = []
    for path in glob.glob(os.path.join(root, "**", "*.py"), recursive=True):
        with open(path) as fh:
            if "TraceAnnotation(" in fh.read():
                holders.append(os.path.relpath(path, root))
    assert holders == [os.path.join("utils", "profiling.py")]
