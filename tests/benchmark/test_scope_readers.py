"""The scope readers on hand-made traces and maps: device time by the
program's named scopes, the share of device time inside recorded
programs, a program's temporaries.  No chip, no JAX: the join works on
plain event lists and plain record dicts (``benchmarks/scope_reduce.py``)."""

import os
import re
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import run as harness  # noqa: E402 - the harness's own look-up by name
import scope_reduce  # noqa: E402
import trace_reduce  # noqa: E402

US = 1000  # the events below are in microseconds
WINDOW = (0, 200 * US)


def ev(name, start_us, dur_us):
    return (name, int(start_us * US), int(dur_us * US))


def reader(kind):
    return harness.load_module("readers", kind).read


def run_of(devices):
    steps = [ev("bench_step_0", 0, 100), ev("bench_step_1", 100, 100)]
    return types.SimpleNamespace(trace=trace_reduce.Trace(devices, steps, []))


# A step is 100 us and step 1 repeats step 0.  jit_iterate runs 10..70: a
# while (10..60) whose body holds a feature fusion (12..32: its own
# op_name under a.features, an epilogue scope nested in it), a product
# fusion (32..52: the convolution inside is a.products', the fusion's own
# op_name and the prox fused into it are a.prox's) and a solve (52..58,
# a.solve); after the loop a copy under no scope (60..70).  The while
# itself keeps 10..12 and 58..60.  jit_other runs 80..90 with one
# operation that happens to be called fusion.1 too.
P = "jit(iterate)/while/body/"
OPS = [ev("%while.9 = (s32[], f32[8]) while(%t), body=%b", 10, 50),
       ev("%fusion.1 = bf16[8,8] fusion(%p), kind=kOutput", 12, 20),
       ev("%fusion.2 = f32[8] fusion(%fusion.1)", 32, 20),
       ev("%custom-call.3 = f32[8] custom-call(%fusion.2)", 52, 6),
       ev("%copy.4 = f32[8] copy(%gte)", 60, 10),
       ev("%fusion.1 = f32[4] fusion(%q)", 80, 10)]
OPS = OPS + [(n, s + 100 * US, d) for n, s, d in OPS]
MODULES = [ev("jit_iterate(77)", 10, 60), ev("jit_other(5)", 80, 10),
           ev("jit_iterate(77)", 110, 60), ev("jit_other(5)", 180, 10)]
ITERATE = {"module": "jit_iterate", "temp_bytes": 5000, "scopes": {
    "while.9": ["jit(iterate)/while"],
    "fusion.1": [P + "a.features/b.epilogue/mul",
                 ["convolution", P + "a.features/dot_general"],
                 ["multiply", P + "a.features/b.epilogue/mul"]],
    "fusion.2": [P + "a.prox/max",
                 ["maximum", P + "a.prox/max"],
                 ["fusion", P + "a.products/dot_general"],
                 ["convolution", P + "a.products/dot_general"],
                 ["dot", P + "a.late/dot_general"]],
    "custom-call.3": [P + "a.solve/triangular_solve"],
    "copy.4": [""]}}
OTHER = {"module": "jit_other", "temp_bytes": 70,
         "scopes": {"fusion.1": ["jit(other)/c.stage/add"]}}
RECORDS = [ITERATE, OTHER]
DEVICES = {"/device:TPU:0": (MODULES, OPS)}


@pytest.fixture
def recorded(monkeypatch):
    def use(records):
        monkeypatch.setattr(scope_reduce, "program_records", lambda: records)
    use(RECORDS)
    return use


def scope_ms(run, module, scope):
    return reader("scope_dev_ms")(run, {"module": module, "scope": scope})


@pytest.mark.parametrize("module,scope,want_us", [
    ("^jit_iterate$", r"a\.features", 20),   # the fusion's convolution names it
    ("^jit_iterate$", r"a\.products", 20),   # not a.prox, the fusion's own op_name
    ("^jit_iterate$", r"a\.prox", None),     # fused into the product: no operation of its own
    ("^jit_iterate$", r"a\.late", None),     # a fusion's second product names nothing
    ("^jit_iterate$", r"a\.solve", 6),       # no product inside: its own op_name
    ("^jit_iterate$", r"b\.epilogue", None),  # nested in a.features, whose convolution names the fusion
    ("^jit_iterate$", r"a\.", 46),           # every a.* scope once
    ("^jit_iterate$", r"^while$", 50),       # the loop and its body, each once
    ("^jit_iterate$", r"c\.stage", None),    # another module's scope
    ("^jit_other$", r"c\.stage", 10),        # fusion.1 of the module it started in
    ("^jit_", r"\.", 56),                    # both modules, every dotted scope
], ids=lambda v: "" if v is None or isinstance(v, int) else v)
def test_an_operation_belongs_to_the_execution_it_starts_in_and_to_one_scope(
        recorded, module, scope, want_us):
    got = scope_ms(run_of(DEVICES), module, scope)
    assert got == (None if want_us is None else pytest.approx(want_us / 1e3))


def test_nested_scopes_count_once_under_the_outermost_that_matches():
    by_scope, found = scope_reduce.scope_ns(
        MODULES, OPS, RECORDS, "^jit_iterate$", r"^[ab]\.", *WINDOW)
    assert found == 1.0
    assert by_scope == {"a.features": 40 * US, "a.products": 40 * US,
                        "a.solve": 12 * US}
    rx = re.compile(r"epilogue|features")
    assert scope_reduce.scope_of(P + "a.features/b.epilogue/mul", rx) == "a.features"
    assert scope_reduce.scope_of("jit(f)/copy", rx) is None


def test_a_loop_keeps_the_time_its_body_leaves():
    ns = {op_name: ns for ns, op_name in scope_reduce.joined(
        MODULES[:1], OPS[:5], RECORDS, "^jit_iterate$", *WINDOW)}
    assert ns["jit(iterate)/while"] == 4 * US and sum(ns.values()) == 60 * US


def test_the_split_closes_on_the_modules_time():
    """Every scope, the loop's own time and what lies under no scope add
    up to the module's time on the module line."""
    by_scope, _ = scope_reduce.scope_ns(
        MODULES, OPS, RECORDS, "^jit_iterate$", r"^(a\..*|while)$", *WINDOW)
    no_scope = 2 * 10 * US  # copy.4
    assert sum(by_scope.values()) + no_scope == trace_reduce.module_ns(
        MODULES, "^jit_iterate$", *WINDOW)


def test_mean_over_the_chips(recorded):
    half = [(n, s, d // 2) for n, s, d in OPS]
    devices = {"/device:TPU:0": (MODULES, OPS), "/device:TPU:1": (MODULES, half)}
    assert scope_ms(run_of(devices), "^jit_iterate$", r"a\.features") == pytest.approx(0.015)


def test_of_two_records_of_one_name_the_one_that_covers_the_execution(recorded):
    elder = {"module": "jit_iterate", "temp_bytes": 9, "scopes": {
        "while.9": ["jit(iterate)/while"],
        "fusion.1": [P + "z.elsewhere/mul"], "fusion.7": [P + "z.elsewhere/add"]}}
    recorded([elder, ITERATE, OTHER])
    run = run_of(DEVICES)
    assert scope_ms(run, "^jit_iterate$", r"a\.features") == pytest.approx(0.020)
    assert scope_ms(run, "^jit_iterate$", r"z\.elsewhere") is None


@pytest.mark.parametrize("lost,want", [("custom-call.3", None), (None, 0.020)],
                         ids=["lacks_10_percent", "whole"])
def test_under_99_percent_found_gives_none_not_a_number(recorded, lost, want):
    """custom-call.3 is 6 of the execution's 60 us: a record without it
    is another program's, the execution finds none, and nothing is read."""
    scopes = {k: v for k, v in ITERATE["scopes"].items() if k != lost}
    recorded([{**ITERATE, "scopes": scopes}, OTHER])
    got = scope_ms(run_of(DEVICES), "^jit_iterate$", r"a\.features")
    assert got == (None if want is None else pytest.approx(want))


def test_98_percent_found_over_the_matched_modules_gives_none(recorded):
    """Two modules match; the short one (2 % of their time) has no
    record: 98 % found."""
    ops = [ev("%fusion.1", 0, 98), ev("%fusion.1", 100, 2)]
    mods = [ev("jit_a(1)", 0, 98), ev("jit_b(2)", 100, 2)]
    a = {"module": "jit_a", "scopes": {"fusion.1": ["jit(a)/s.one/add"]}}
    b = {"module": "jit_b", "scopes": {"fusion.1": ["jit(b)/s.one/add"]}}
    run = run_of({"/device:TPU:0": (mods, ops)})
    recorded([a])
    assert scope_ms(run, "^jit_", r"s\.one") is None
    assert scope_ms(run, "^jit_a$", r"s\.one") == pytest.approx(0.049)
    recorded([a, b])
    assert scope_ms(run, "^jit_", r"s\.one") == pytest.approx(0.050)


@pytest.mark.parametrize("kind,params", [
    ("scope_dev_ms", {"module": "^jit_iterate$", "scope": r"a\."}),
    ("recorded_dev_pct", {}),
    ("program_bytes", {"field": "temp_bytes"}),
])
@pytest.mark.parametrize("case", ["no_trace", "no_record", "records_with_errors"])
def test_nothing_to_read_gives_none_and_raises_nothing(recorded, kind, params, case):
    run = types.SimpleNamespace(trace=None) if case == "no_trace" else run_of(DEVICES)
    recorded({"no_trace": RECORDS, "no_record": [], "records_with_errors": [
        {"module": "jit_iterate", "error": "TypeError: gone"}]}[case])
    assert reader(kind)(run, params) is None


def test_a_program_from_before_the_records_has_none(monkeypatch):
    from libskylark_tpu.utils import profiling

    monkeypatch.delattr(profiling, "records")
    assert scope_reduce.program_records() == []
    assert scope_ms(run_of(DEVICES), "^jit_iterate$", r"a\.") is None


@pytest.mark.parametrize("records,want", [
    (RECORDS, 100.0),
    ([ITERATE], 100.0 * 60 / 70),  # jit_other's 10 us in no recorded program
    ([OTHER], 100.0 * 10 / 70),
    ([{**ITERATE, "scopes": {k: v for k, v in ITERATE["scopes"].items()
                             if k != "custom-call.3"}}, OTHER], 100.0 * 10 / 70),
], ids=["all", "one_module_unrecorded", "the_long_module_unrecorded", "a_record_that_covers_too_little"])
def test_share_of_device_time_inside_recorded_programs(recorded, records, want):
    recorded(records)
    assert reader("recorded_dev_pct")(run_of(DEVICES), {}) == pytest.approx(want)


def test_device_work_outside_every_execution_counts_as_unrecorded(recorded):
    ops = OPS + [ev("%eager.1", 95, 5)]
    got = reader("recorded_dev_pct")(run_of({"/device:TPU:0": (MODULES, ops)}), {})
    assert got == pytest.approx(100.0 * 140 / 145)


@pytest.mark.parametrize("field,records,want", [
    ("temp_bytes", RECORDS, 5000),
    ("temp_bytes", RECORDS + [{"module": "jit_never_ran", "temp_bytes": 10**9,
                               "scopes": {}}], 5000),
    ("temp_bytes", [OTHER, {"module": "jit_iterate", "error": "gone"}], 70),
    ("argument_bytes", RECORDS, None),
], ids=["largest", "of_the_modules_that_ran", "a_record_with_an_error_has_no_bytes", "a_field_no_record_has"])
def test_a_programs_bytes_are_the_largest_among_the_recorded_modules_that_ran(
        recorded, field, records, want):
    recorded(records)
    assert reader("program_bytes")(run_of(DEVICES), {"field": field}) == want


def test_an_instruction_is_found_by_the_80_characters_an_event_keeps(recorded):
    long = "fusion_" + "x" * 100
    recorded([{"module": "jit_a", "scopes": {long: ["jit(a)/s.one/add"]}}])
    run = run_of({"/device:TPU:0": ([ev("jit_a(1)", 0, 10)],
                                    [ev(f"%{long} = f32[] fusion()", 0, 10)])})
    assert scope_ms(run, "^jit_a$", r"s\.one") == pytest.approx(0.005)


def test_an_execution_belongs_to_the_window_its_middle_lies_in():
    """The device's stamps stand a little off the host's: the first
    execution of the window may start before it and counts whole; one
    after it, or one of the step before that ends before the window opens,
    does not."""
    early = [(n, s - 11 * US, d) for n, s, d in OPS[:5]]  # starts 1 us before lo
    mods = [ev("jit_iterate(77)", -1, 60), MODULES[2], ev("jit_iterate(77)", 210, 60),
            ev("jit_iterate(77)", -70, 60)]
    ops = early + OPS[6:11] + [(n, s + 200 * US, d) for n, s, d in OPS[:5]] + [
        (n, s - 80 * US, d) for n, s, d in OPS[:5]]
    by_scope, found = scope_reduce.scope_ns(
        mods, ops, RECORDS, "^jit_iterate$", r"a\.features", *WINDOW)
    assert by_scope == {"a.features": 40 * US} and found == 1.0
    assert scope_reduce.modules_run({"d": (mods, ops)}, *WINDOW) == {"jit_iterate"}
    assert scope_reduce.recorded_ns(mods, ops, RECORDS, *WINDOW) == (120 * US, 120 * US)
