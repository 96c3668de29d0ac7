"""The benchmark off the chip: that the manifest and the data files name
each other consistently, that the yardstick's arithmetic (trace
reduction, cost functions) gives known answers, that ``run.py`` refuses
to run without a TPU, and that every entry kind rehearses end to end at
a tiny size with ``correct`` true for the program and false for the
control and for each planted fault.

The rehearsals run in child processes (x64 off, as the driver runs the
benchmark; the suite's conftest turns it on for the parent only), one
JAX start an entry kind.
"""

import importlib.util
import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)

import run as harness  # noqa: E402 - the harness's own look-up by name
import trace_reduce  # noqa: E402
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(*parts):
    with open(os.path.join(REPO, *parts)) as fh:
        return json.load(fh)


def module(directory, name):
    path = os.path.join(BENCH, directory, name + ".py")
    spec = importlib.util.spec_from_file_location(f"t_{directory}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


MANIFEST = load("BENCHMARK.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
# every cell file rehearses, also one that is not, or not yet, in the manifest
CELL_FILES = sorted(os.path.splitext(f)[0]
                    for f in os.listdir(os.path.join(BENCH, "workloads")))
METRICS = [("end_to_end", m["name"]) for m in MANIFEST["end_to_end"]] + [
    ("layer_metrics", m["name"]) for m in MANIFEST["per_layer"]]


def reports(cell, group):
    return {m["name"] for m in harness.cell_metrics(MANIFEST, group, cell)}


# -- the manifest and the data files ----------------------------------------


def test_manifest_has_the_contracts_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "benchmarks/run.py"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    cells = len(MANIFEST["workloads"])
    # a full check: 2 + 14 runs a cell, each run_seconds + 60, 180 s a cell
    # to compile, 1200 s spare, inside 43200 s -- with the full 24 cells
    assert 1200 + 24 * 180 + (2 + 14 * 24) * (MANIFEST["run_seconds"] + 60) <= 43200
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, cells // 4)
    names = [m["name"] for g in ("end_to_end", "per_layer") for m in MANIFEST[g]]
    assert len(names) == len(set(names))
    assert len(json.dumps(MANIFEST)) < 64 * 1024
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in MANIFEST["end_to_end"])


@pytest.mark.parametrize("config", MANIFEST["configs"], ids=lambda c: c["name"])
def test_configuration_file_is_under_paths_and_states_its_cuts(config):
    assert NAME.match(config["name"]) and len(config["source"]) <= 200
    assert any(config["file"].startswith(p + "/") for p in MANIFEST["paths"])
    body = load(config["file"])
    assert body["name"] == config["name"] and body["source"] == config["source"]
    assert sorted(config["reduced"]) == sorted(body["reduced"])
    for key in config["reduced"]:  # a cut names a size of the file, never a width
        assert key in body and not key.endswith(("_dim", "_rank"))
    assert set(body["rehearsal"]) <= set(body)
    assert any(w["config"] == config["name"] for w in MANIFEST["workloads"])


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_cell_names_a_configuration_an_entry_and_metrics_that_exist(cell):
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    assert any(c["name"] == cell["config"] for c in MANIFEST["configs"])
    body = load("benchmarks", "workloads", cell["name"] + ".json")
    for key in ("name", "config", "traffic", "chips", "why"):
        assert body[key] == cell[key], key
    entry = module("entries", body["entry"]["kind"])
    assert callable(entry.Entry) and isinstance(entry.COSTS, dict)
    assert body["limits"] and all(v > 0 for v in body["limits"].values())
    e2e = reports(cell["name"], "end_to_end")
    assert "setup_s" in e2e and len(e2e) >= 2
    assert reports(cell["name"], "per_layer")


@pytest.mark.parametrize("group,name", METRICS, ids=lambda v: v)
def test_metric_file_agrees_with_the_manifest_and_its_reader_exists(group, name):
    key = "end_to_end" if group == "end_to_end" else "per_layer"
    row = next(m for m in MANIFEST[key] if m["name"] == name)
    body = load("benchmarks", group, name + ".json")
    assert NAME.match(name) and UNIT.match(row["unit"])
    assert row["better"] in ("lower", "higher") and row["source"] in SOURCES
    for k, v in row.items():
        if k not in ("bound", "workloads"):  # the manifest's alone, so that a
            assert body[k] == v, k           # later cell edits no file
    reader = body["reader"]
    assert callable(module("readers", reader["kind"]).read)
    if "module" in reader:
        re.compile(reader["module"])
    assert set(row.get("workloads", [])) <= set(CELLS)
    cells = [c for c in CELLS if name in reports(c, key)]
    assert cells, "a metric that no cell reports"
    for cell in cells:
        kind = load("benchmarks", "workloads", cell + ".json")["entry"]["kind"]
        if "cost" in reader:
            assert reader["cost"] in module("entries", kind).COSTS
        if key == "per_layer":  # every cell that reports it reports what it moves
            assert row["moves"] in reports(cell, "end_to_end")
            assert row["layer"] and "\n" not in row["layer"]


def test_peaks_table_raises_on_an_unknown_device_kind():
    peaks = load("benchmarks", "peaks.json")
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks["cpu"]


# -- the trace reduction, on a hand-made trace ------------------------------

US = 1000  # the events below are in microseconds


def ev(name, start_us, dur_us):
    return (name, start_us * US, dur_us * US)


@pytest.fixture(scope="module")
def trace():
    modules = [ev("jit_run(11)", 10, 30), ev("jit_qr(5)", 50, 10),
               ev("jit_run(11)", 110, 30), ev("jit_qr(5)", 150, 10),
               ev("jit_other(9)", 300, 50)]          # after the steps
    ops = [ev("%while.1 = (f32[8]) while(...)", 10, 30),    # holds the next two
           ev("%fusion.1 = f32[8] fusion(...)", 10, 18), ev("%fusion.2", 28, 12),
           ev("%qr.3", 50, 10),
           ev("%while.1 = (f32[8]) while(...)", 110, 30),
           ev("%fusion.1 = f32[8] fusion(...)", 110, 18), ev("%fusion.2", 128, 12),
           ev("%qr.3", 150, 10), ev("%fusion.9", 300, 50)]
    steps = [ev("bench_step_0", 0, 100), ev("bench_step_1", 105, 95)]
    host = [ev("solve", 0, 100), ev("float()", 40, 12), ev("gc", 100, 5),
            ev("solve", 105, 95), ev("float()", 140, 12)]
    return trace_reduce, trace_reduce.Trace({"/device:TPU:0": (modules, ops)},
                                            steps, host)


def test_busy_union_and_idle_share(trace):
    tr, t = trace
    assert tr.merge([(5, 9), (1, 3), (2, 6)]) == [[1, 9]]
    # a step: 10..40 busy (a while and the two ops in it) and 50..60; two steps
    assert t.window_s == pytest.approx(200e-6)
    assert t.busy_s == pytest.approx(80e-6)
    assert t.idle_pct() == pytest.approx(60.0)
    assert tr.busy_ns([ev("x", 0, 50)], 20 * US, 30 * US) == 10 * US  # clipped


def test_time_per_module_and_launches(trace):
    tr, t = trace
    assert tr.strip_id("jit_run(11)") == "jit_run"
    assert t.module_s("^jit_run$") == pytest.approx(60e-6)
    assert t.module_s("^jit_(run|qr)$") == pytest.approx(80e-6)
    assert t.module_s("^jit_other$") == 0.0      # outside the traced steps
    assert t.launches() == 4 and t.n_steps == 2


def test_gaps_are_attributed_to_the_step_and_the_host_span(trace):
    tr, t = trace
    b = t.breakdown()
    # self time: the while's 30 us are its two operations', none its own
    assert b["device_ops"] == [["%fusion.1", pytest.approx(36e-6)],
                               ["%fusion.2", pytest.approx(24e-6)],
                               ["%qr.3", pytest.approx(20e-6)]]
    assert b["device_modules"][0] == ["jit_run", pytest.approx(60e-6)]
    gaps = dict((name, secs) for name, secs in b["idle_gaps"])
    assert len(b["idle_gaps"]) == 5
    # 60..110: the end of step 0, the gap between the steps, the start of step 1
    assert b["idle_gaps"][0] == ["bench_step_0: solve", pytest.approx(50e-6)]
    assert gaps["bench_step_1: solve"] == pytest.approx(40e-6)   # 160..200
    assert gaps["bench_step_0: float()"] == pytest.approx(10e-6)  # 40..50
    assert tr.attribute((100 * US, 104 * US), t.steps, t.host) == "between steps: gc"


# -- the cost functions, against a hand count -------------------------------


def test_lsqr_cost_is_two_reads_of_A_an_iteration():
    cost = module("entries", "ls_solve").COSTS["lsqr"]
    flop, nbytes = cost({"m": 8, "n": 4}, {"lsqr_iters": 3})
    assert nbytes == 2 * 3 * (8 * 4 * 4)       # two passes, three iterations, f32
    assert flop == 2 * 3 * (2 * 8 * 4)         # a multiply and an add an entry a pass


def test_fjlt_cost_reads_the_operand_once_and_writes_the_sketch():
    cost = module("entries", "ls_solve").COSTS["fjlt"]
    flop, nbytes = cost({"m": 16, "n": 3, "s": 8}, {})
    assert nbytes == 4 * (16 * 4 + 8 * 4)      # [A b] in, S [A b] out, f32
    assert flop == 16 * 4 * 4                  # log2(16) adds an entry


def test_krr_cost_counts_five_feature_passes_one_gram_four_products():
    cost = module("entries", "krr_train").COSTS["krr"]
    n, d, s, t = 10, 3, 4, 2
    flop, nbytes = cost({"rows": n, "d": d, "s": s, "targets": t, "sweeps": 2}, {})
    assert flop == 5 * 2 * n * d * s + 2 * n * s * s + 4 * 2 * n * s * t
    assert nbytes == 5 * 2 * n * d             # X in bf16, once a pass


# -- run.py, as the driver starts it ----------------------------------------


def child(script, args, tmp_path, timeout=900):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("SKYLARK_") and k != "XLA_FLAGS"}
    # The child's cache goes where the environment says, not into the checkout.
    env.update(JAX_PLATFORMS="cpu", BENCH_RUN="7",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run([sys.executable, script, *args], env=env,
                          cwd=str(tmp_path), capture_output=True, text=True,
                          timeout=timeout)
    return proc, [ln for ln in proc.stdout.splitlines() if ln.strip()]


def test_without_a_tpu_it_fails_and_prints_no_result(tmp_path):
    proc, lines = child(os.path.join(BENCH, "run.py"),
                        ["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                         "--trace", "0"], tmp_path, timeout=300)
    assert proc.returncode != 0
    assert not any('"metrics"' in ln for ln in lines)
    assert "TPU" in proc.stderr


_REHEARSED: dict = {}


def rehearsed(kind, tmp_path_factory):
    """One child an entry kind, whatever the number of tests that read it."""
    if kind not in _REHEARSED:
        proc, lines = child(
            os.path.join(REPO, "tests", "benchmark", "_drive_child.py"), [kind],
            tmp_path_factory.mktemp(kind))
        assert proc.returncode == 0, proc.stderr[-3000:]
        _REHEARSED[kind] = json.loads(lines[-1])
    return _REHEARSED[kind]


def kind_of(cell):
    return load("benchmarks", "workloads", cell + ".json")["entry"]["kind"]


@pytest.mark.parametrize("cell", CELL_FILES)
def test_rehearsal_runs_the_cell_end_to_end_and_is_correct(cell, tmp_path_factory):
    got = rehearsed(kind_of(cell), tmp_path_factory)[cell]["sound"]
    assert got["rc"] == 0 and got["correct"] is True
    assert got["attempted"] >= 1 and got["failed"] == 0
    for value, limit in got["compared"].values():
        assert value <= limit


@pytest.mark.parametrize("cell", CELL_FILES)
def test_control_in_the_precision_below_is_not_correct(cell, tmp_path_factory):
    got = rehearsed(kind_of(cell), tmp_path_factory)[cell]
    assert all(got["program"][n] <= lim for n, lim in got["limits"].items())
    assert any(got["control"][n] > lim for n, lim in got["limits"].items())
    assert any(got["control"][n] >= 3 * got["program"][n] for n in got["limits"])


@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out"])
@pytest.mark.parametrize("cell", CELL_FILES)
def test_broken_timed_path_is_not_correct(cell, fault, tmp_path_factory):
    got = rehearsed(kind_of(cell), tmp_path_factory)[cell][fault]
    assert got["rc"] == 0 and got["failed"] == 0 and got["attempted"] >= 1
    assert got["correct"] is False
    assert any(value > limit for value, limit in got["compared"].values())
