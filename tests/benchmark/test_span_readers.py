"""The span readers on hand-made traces: idle time by stage, device time
by the stage that launched it, host events by stage.  No chip, no JAX:
the readers work on plain event lists (``benchmarks/span_reduce.py``)."""

import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import run as harness  # noqa: E402 - the harness's own look-up by name
import span_reduce  # noqa: E402
import trace_reduce  # noqa: E402

US = 1000  # the events below are in microseconds
STAGE = r"^skylark:[a-z_]+\."
LAUNCH = r"^Execute$"


def ev(name, start_us, dur_us):
    return (name, start_us * US, dur_us * US)


def reader(kind):
    return harness.load_module("readers", kind).read


def run_of(modules, ops, host, steps=None):
    steps = steps or [ev("bench_step_0", 0, 100), ev("bench_step_1", 100, 100)]
    trace = trace_reduce.Trace({"/device:TPU:0": (modules, ops)}, steps, host)
    return types.SimpleNamespace(trace=trace)


def launch(function, at_us):
    """A host call that enqueues one program: the ``PjitFunction`` and
    the runtime's marker inside it."""
    return [ev(f"PjitFunction({function})", at_us, 3), ev("Execute", at_us + 1, 1)]


# A step is 100 us.  The device runs 10..40 and 60..70 of each, so it
# idles 0..10, 40..60 and 70..100: 60 us a step.  The host is inside
# a.one 5..50 (a.inner nested in it 20..48) and a.two 50..65, all inside
# the entry span; step 1 repeats step 0.
OPS = [ev("%fusion.1", 10, 30), ev("%fusion.2", 60, 10),
       ev("%fusion.1", 110, 30), ev("%fusion.2", 160, 10)]
MODULES = [ev("jit_run(7)", 10, 30), ev("jit_qr(3)", 60, 10),
           ev("jit_run(7)", 110, 30), ev("jit_qr(3)", 160, 10)]
HOST = [ev("skylark:solve", 0, 100), ev("skylark:a.one", 5, 45),
        ev("skylark:a.inner", 20, 28), ev("skylark:a.two", 50, 15),
        ev("skylark:solve", 100, 100), ev("skylark:a.one", 105, 45),
        ev("skylark:a.inner", 120, 28), ev("skylark:a.two", 150, 15),
        ev("lower_sharding_computation", 30, 5), ev("lower_sharding_computation", 52, 2),
        ev("lower_sharding_computation", 130, 5), ev("$python frame", 0, 200),
        *launch("run", 6), *launch("qr", 51), *launch("run", 106), *launch("qr", 151)]


@pytest.mark.parametrize("span,invert,want_us", [
    (r"^skylark:a\.one$", False, 15),     # 5..10 inside, 40..50 up to its edge
    (r"^skylark:a\.two$", False, 10),     # 50..60: the gap runs across the edge
    (r"^skylark:a\.", False, 25),         # a.inner nested in a.one: counted once
    (r"^skylark:a\.inner$", False, 8),    # 40..48
    (STAGE, True, 35),                    # 0..5 and 70..100: inside no stage
    (r"^skylark:solve$", False, 60),      # the entry span holds every gap
    (r"^skylark:solve$", True, 0),
    (r"^skylark:nothing$", False, 0),
], ids=["inside", "across-edge", "nested-once", "inner", "invert", "entry",
        "entry-inverted", "no-match"])
def test_idle_is_put_down_to_the_spans_the_host_was_in(span, invert, want_us):
    got = reader("span_idle_ms")(run_of(MODULES, OPS, HOST),
                                 {"span": span, "invert": invert})
    assert got == pytest.approx(want_us / 1000)  # ms a step


def test_idle_by_stage_and_outside_every_stage_add_up_to_the_idle_time():
    run = run_of(MODULES, OPS, HOST)
    read = reader("span_idle_ms")
    parts = read(run, {"span": STAGE}) + read(run, {"span": STAGE, "invert": True})
    t = run.trace
    assert parts == pytest.approx(1e3 * (t.window_s - t.busy_s) / t.n_steps)


def test_interval_arithmetic():
    sr = span_reduce
    assert sr.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert sr.overlap_ns([], [(0, 5)]) == 0
    assert sr.cover([ev("a", 0, 10), ev("b", 5, 10)], 2 * US, 50 * US) == [
        (2 * US, 15 * US)]
    assert sr.cover([ev("a", 10, 10)], 0, 50 * US, invert=True) == [
        (0, 10 * US), (20 * US, 50 * US)]
    assert sr.module_of("_einsum") == "jit__einsum"
    assert sr.module_of("<lambda>") == "jit__lambda_"


@pytest.mark.parametrize("span,want_us", [
    # run is enqueued at 7 inside a.one and runs 10..40; qr is enqueued at
    # 52 inside a.two (closed at 65) and runs 60..70: both count in full
    (r"^skylark:a\.one$", 30), (r"^skylark:a\.two$", 10), (STAGE, 40),
    (r"^skylark:a\.inner$", 0),
], ids=["one", "runs-after-the-span-closed", "all", "launched-nothing"])
def test_device_time_goes_to_the_span_its_launch_started_in(span, want_us):
    got = reader("span_launched_ms")(run_of(MODULES, OPS, HOST),
                                     {"span": span, "launch": LAUNCH})
    assert got == pytest.approx(want_us / 1000)


def test_a_call_made_while_tracing_and_a_nested_call_launch_nothing():
    host = [ev("skylark:k.lift", 0, 20), ev("skylark:k.segment", 20, 30),
            ev("PjitFunction(add)", 2, 2),       # under make_jaxpr: no marker
            ev("PjitFunction(run)", 21, 25),     # the slow path: traces, then runs
            ev("PjitFunction(multiply)", 23, 2),  # nested, trace-time
            ev("Execute", 44, 1)]
    assert span_reduce.launches(host, LAUNCH) == [("jit_run", 44 * US)]
    run = run_of([ev("jit_run(1)", 60, 30)], [ev("%f", 60, 30)], host,
                 steps=[ev("bench_step_0", 0, 100)])
    read = reader("span_launched_ms")
    assert read(run, {"span": r"segment$", "launch": LAUNCH}) == pytest.approx(0.030)
    assert read(run, {"span": r"lift$", "launch": LAUNCH}) == 0.0


def test_a_launch_before_the_window_shifts_nothing():
    """Warm-up launches lie before the traced steps on both lines."""
    host = [*launch("warm", -30), *HOST]
    modules = [ev("jit_warm(1)", -25, 5), *MODULES]
    run = run_of(modules, [ev("%w", -25, 5), *OPS], host)
    got = reader("span_launched_ms")(run, {"span": STAGE, "launch": LAUNCH})
    assert got == pytest.approx(0.040)


@pytest.mark.parametrize("modules", [
    # an execution nobody launched, in the middle: every later pair is off
    [MODULES[0], ev("jit_ghost(9)", 45, 10), *MODULES[1:]],
    # an execution past the last launch
    [*MODULES, ev("jit_ghost(9)", 180, 15)],
    # a launch whose execution the trace lacks
    MODULES[1:],
], ids=["ghost-in-the-middle", "ghost-at-the-end", "module-missing"])
def test_an_unmatched_module_gives_none_not_a_number(modules):
    run = run_of(modules, OPS, HOST)
    assert reader("span_launched_ms")(run, {"span": STAGE, "launch": LAUNCH}) is None


@pytest.mark.parametrize("dur_us,want", [(0.5, 0.040), (2, None)],
                         ids=["under-1-percent", "over-1-percent"])
def test_a_pair_found_by_order_under_another_name_counts_as_not_found(dur_us, want):
    # JAX reuses one executable under another function's name for some
    # one-operation programs: up to 1 % of the module time may be such
    modules = [*MODULES, ev("jit_convert_element_type(2)", 190, dur_us)]
    run = run_of(modules, OPS, [*HOST, *launch("squeeze", 185)])
    got = reader("span_launched_ms")(run, {"span": STAGE, "launch": LAUNCH})
    assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize("span,event,want", [
    (None, r"^lower_sharding_computation$", 1.5),    # three in two steps
    (r"^skylark:a\.one$", r"^lower_sharding_computation$", 1.0),
    (r"^skylark:a\.two$", r"^lower_sharding_computation$", 0.5),
    (STAGE, r"^Execute$", 2.0),
    (r"^skylark:nothing$", r"^Execute$", 0.0),
], ids=["anywhere", "in-one", "in-two", "launches", "no-match"])
def test_host_events_are_counted_by_the_span_they_start_in(span, event, want):
    got = reader("span_event_count")(run_of(MODULES, OPS, HOST),
                                     {"span": span, "event": event})
    assert got == pytest.approx(want)


@pytest.mark.parametrize("kind,params", [
    ("span_idle_ms", {"span": STAGE}),
    ("span_idle_ms", {"span": STAGE, "invert": True}),
    ("span_launched_ms", {"span": STAGE, "launch": LAUNCH}),
    ("span_event_count", {"span": STAGE, "event": r"^Execute$"}),
], ids=["idle", "idle-inverted", "launched", "count"])
def test_a_program_without_spans_reads_nothing_and_raises_nothing(kind, params):
    bare = [e for e in HOST if not e[0].startswith("skylark:")]
    assert reader(kind)(run_of(MODULES, OPS, bare), params) is None
    assert reader(kind)(types.SimpleNamespace(trace=None), params) is None


def test_a_count_anywhere_needs_no_span():
    bare = [e for e in HOST if not e[0].startswith("skylark:")]
    got = reader("span_event_count")(
        run_of(MODULES, OPS, bare),
        {"span": None, "event": r"^lower_sharding_computation$"})
    assert got == pytest.approx(1.5)


def test_mean_over_the_chips():
    idle_less = [ev("%fusion.1", 0, 50), ev("%fusion.2", 50, 50),
                 ev("%fusion.1", 100, 100)]           # never idle
    trace = trace_reduce.Trace(
        {"/device:TPU:0": (MODULES, OPS), "/device:TPU:1": (MODULES, idle_less)},
        [ev("bench_step_0", 0, 100), ev("bench_step_1", 100, 100)], HOST)
    got = reader("span_idle_ms")(types.SimpleNamespace(trace=trace),
                                 {"span": r"^skylark:a\.", "invert": False})
    assert got == pytest.approx(0.0125)
