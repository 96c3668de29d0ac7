"""The entry kind ``krr_ppt`` off the chip: what the cell reports, its
manifest row against its file, the TensorSketch cost against a hand
count, the ``scope_roofline`` reader on a hand-made trace, and the
rehearsal problem in this process (x64 off, as the benchmark runs): the
program is ``correct``; the fp8 control, the reference with one level
dropped and the harness's planted faults are not.

``test_benchmark.py`` rehearses the cell through ``run.py`` in a child
process as it does every cell file; this file holds what is the entry's
own.
"""

import importlib.util
import math
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)

import run as harness  # noqa: E402 - the harness's own look-up by name
import scope_reduce  # noqa: E402
import trace_reduce  # noqa: E402

CELL = "krr_poly2_mnist8m_resident"


def entry_module():
    path = os.path.join(BENCH, "entries", "krr_ppt.py")
    spec = importlib.util.spec_from_file_location("t_krr_ppt_entry", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ENTRY = entry_module()
MANIFEST, CELL_FILE, CONFIG = harness.load_cell(CELL)


# -- what the cell reports, and its rows in the manifest ---------------------


def test_the_cell_reports_the_trainers_metrics_and_its_own_five():
    per_layer = {m["name"] for m in harness.cell_metrics(MANIFEST, "per_layer", CELL)}
    assert per_layer == {
        "device_idle_pct.train", "sweep_ms", "program_build_idle_ms", "lowerings_per_call",
        "unattributed_idle_ms.train", "program_temp_bytes.train", "recorded_dev_pct.train",
        "krr_feature_pass_dev_ms", "krr_gram_product_dev_ms",
        "ppt_feature_dev_ms", "ppt_dft_dev_ms", "ppt_hash_dev_ms", "krr_feature_passes",
        "ppt_roofline"}
    e2e = {m["name"] for m in harness.cell_metrics(MANIFEST, "end_to_end", CELL)}
    assert e2e == {"train_rows_per_s", "setup_s"}
    # the Gaussian map's roofline counts a GEMM this cell does not run
    assert CELL not in next(m for m in MANIFEST["per_layer"]
                            if m["name"] == "krr_roofline")["workloads"]


def test_the_manifest_rows_are_the_files():
    row = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert row == {k: CELL_FILE[k] for k in ("name", "config", "traffic", "chips", "why")}
    assert row["chips"] == 1 and CELL_FILE["entry"] == {"kind": "krr_ppt"}
    conf = next(c for c in MANIFEST["configs"] if c["name"] == CELL_FILE["config"])
    assert conf["source"] == CONFIG["source"] and conf["reduced"] == list(CONFIG["reduced"])
    assert conf["file"] == f"benchmarks/configs/{CONFIG['name']}.json"


def test_the_configuration_is_the_source_with_one_cut():
    assert CONFIG["architecture"] is None and CONFIG["kernel"] == "polynomial"
    assert (CONFIG["d"], CONFIG["targets"], CONFIG["s"]) == (784, 10, 4096)
    assert (CONFIG["q"], CONFIG["c"], CONFIG["gamma"]) == (2, 1.0, 1.0 / 784)
    assert list(CONFIG["reduced"]) == ["rows"] and CONFIG["rows_published"] == 8_100_000
    assert CONFIG["rows"] % CONFIG["block_rows"] == 0 and CONFIG["block_rows"] >= 4096
    for key in ("q, c", "s", "gamma, lam", "sweeps", "block_rows", "data", "sketch_seed"):
        assert key in CONFIG["assumed"]
    assert set(CELL_FILE["limits"]) == {"pred_rel_err"} <= set(CELL_FILE["limit_reasons"])


# -- the cost function, against a hand count ---------------------------------


def test_the_cost_counts_hash_transforms_and_products_a_row_a_pass():
    flop, nbytes = ENTRY.COSTS["ppt_features"](
        {"rows": 10, "d": 3, "s": 8, "q": 2}, {"feature_passes": 5})
    # 2 q d = 12, (q + 1) 2.5 S log2 S = 180, 6 (q - 1)(S/2 + 1) = 30
    assert flop == 5 * 10 * (12 + 180 + 30)
    assert nbytes == 5 * 10 * 2 * 3              # X in bf16, once a pass
    flop, nbytes = ENTRY.COSTS["ppt_features"](CONFIG, {"feature_passes": 5})
    assert flop / (5 * CONFIG["rows"]) == 3136 + 368_640 + 12_294
    peaks = harness.load_json(BENCH, "peaks.json")["TPU v5 lite"]
    least = max(flop / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    assert 0.019 < least < 0.021                 # about 20 ms a call


# -- the scope_roofline reader, on a hand-made trace -------------------------

US = 1000


def ev(name, start_us, dur_us):
    return (name, int(start_us * US), int(dur_us * US))


P = "jit(gram)/while/body/krr.features/"
RECORDS = [{"module": "jit_gram", "scopes": {
    "fusion.1": [P + "ppt.dft/dot_general"],
    "fusion.2": [P + "ppt.hash/add", ["convolution", P + "ppt.hash/dot_general"]],
    "fusion.3": ["jit(gram)/while/body/krr.gram_product/dot_general"]}}]
OPS = [ev("%fusion.1 = bf16[8] fusion(%a)", 0, 60), ev("%fusion.2", 60, 20),
       ev("%fusion.3", 80, 20)]


def run_of(passes=5.0, records_ops=OPS):
    trace = trace_reduce.Trace(
        {"/device:TPU:0": ([ev("jit_gram(3)", 0, 100)], records_ops)},
        [ev("bench_step_0", 0, 100)], [])
    return types.SimpleNamespace(
        trace=trace, costs=ENTRY.COSTS, entry=types.SimpleNamespace(sizes={
            "rows": 1000, "d": 10, "s": 16, "q": 2}),
        peaks={"TPU v5 lite": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}},
        device=types.SimpleNamespace(device_kind="TPU v5 lite"),
        info_mean=lambda key: passes if key == "feature_passes" else None)


PARAMS = {"module": "^jit_(gram|zr|apply_delta)$", "scope": r"ppt\.",
          "cost": "ppt_features", "info": ["feature_passes"],
          "flops_peak": "bf16_flops_per_s"}


def test_scope_roofline_is_the_least_time_over_the_scopes_device_time(monkeypatch):
    monkeypatch.setattr(scope_reduce, "program_records", lambda: RECORDS)
    read = harness.load_module("readers", "scope_roofline").read
    # a row: 2*2*10 + 3*2.5*16*4 + 6*1*9 = 40 + 480 + 54 = 574 flop, 20 bytes
    least = max(5 * 1000 * 574 / 1e12, 5 * 1000 * 20 / 1e9)  # 100 us: bytes
    assert read(run_of(), PARAMS) == pytest.approx(100.0 * least / 80e-6)
    # fewer passes, less work over the same time
    assert read(run_of(passes=1.0), PARAMS) == pytest.approx(100.0 * least / 5 / 80e-6)


@pytest.mark.parametrize("case", ["no_record", "no_count", "no_scope", "no_trace"])
def test_scope_roofline_reads_nothing_and_raises_nothing(monkeypatch, case):
    """A parent: no record, no ``feature_passes``, no ``ppt.*`` scope."""
    monkeypatch.setattr(scope_reduce, "program_records",
                        lambda: [] if case == "no_record" else RECORDS)
    read = harness.load_module("readers", "scope_roofline").read
    run = run_of(passes=None if case == "no_count" else 5.0,
                 records_ops=OPS[2:] if case == "no_scope" else OPS)
    if case == "no_trace":
        run.trace = None
    assert read(run, PARAMS) is None


# -- the rehearsal problem, in this process ----------------------------------


@pytest.fixture(scope="module")
def rehearsed():
    """One entry at the rehearsal sizes, its answer and the control's
    (the big seed is the driver's kind)."""
    with jax.enable_x64(False):
        entry = ENTRY.Entry(CONFIG, CELL_FILE, 2**31 + 17, 1, tiny=True)
        entry.setup()
        rec = entry.step()
        entry.release()
        return entry, rec, entry.control()


def compared(entry, answers):
    with jax.enable_x64(False):
        return {name: (value, limit) for name, value, limit in entry.check(answers)}


def test_the_program_is_correct_and_reports_its_passes(rehearsed):
    entry, rec, _ = rehearsed
    assert rec["bad"] is None and rec["info"] == {"feature_passes": 5, "feature_map": "PPT"}
    assert rec["units"] == {"rows": 2 * entry.sizes["rows"]}
    assert rec["answer"].shape == (entry.sizes["s"], 10)
    got = compared(entry, [rec["answer"]])
    assert all(value <= limit for value, limit in got.values()), got


def test_the_fp8_control_and_a_dropped_level_are_not_correct(rehearsed):
    entry, rec, control = rehearsed
    sound = compared(entry, [rec["answer"]])["pred_rel_err"][0]
    value, limit = compared(entry, [control])["pred_rel_err"]
    assert value > limit and value > 3 * sound
    with jax.enable_x64(False):
        one_level = entry.reference(levels=1)
    value, limit = compared(entry, [one_level])["pred_rel_err"]
    assert value > 10 * limit


@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out"])
def test_a_planted_fault_is_not_correct(rehearsed, fault):
    entry, rec, _ = rehearsed
    C = rec["answer"]
    with jax.enable_x64(False):
        if fault == "answer_altered":
            broken = C.at[0, 0].add(jnp.linalg.norm(C))
        else:
            z, X, Y = entry.sizes, entry.X, entry.Y
            entry.X, entry.Y = X[: z["rows"] // 2], Y[: z["rows"] // 2]
            z["rows"] //= 2
            try:
                broken = entry.step()["answer"]
            finally:
                z["rows"] *= 2
                entry.X, entry.Y = X, Y
    got = compared(entry, [broken])
    assert any(value > limit for value, limit in got.values()), got
    assert compared(entry, [C, broken]) == got  # the worst answer decides


def test_the_reference_features_are_the_maps(rehearsed):
    """The reference reads the draws and nothing else of the program: its
    features of some rows are the map's own (f32, the FFT route) to f32
    rounding."""
    entry, _, _ = rehearsed
    from libskylark_tpu import SketchContext

    z = entry.sizes
    with jax.enable_x64(False):
        M = entry.kernel().create_rft(z["s"], "regular", SketchContext(seed=z["sketch_seed"]))
        X = entry.X[:64].astype(jnp.float32)
        want = np.asarray(M.apply(X, "rowwise"))
        got = np.asarray(ENTRY.features(X, entry.H, entry.idx, entry.val, z["gamma"], z["c"]))
    assert np.abs(got - want).max() < 1e-5 * math.sqrt(z["s"]) * np.abs(want).max()


def test_a_program_whose_map_hoists_nothing_is_refused_at_setup(monkeypatch):
    """The parent's PPT had no operands to hoist: the cell refuses it at
    once, before any data or compilation."""
    from libskylark_tpu.sketch import PPT, base

    monkeypatch.setattr(PPT, "hoistable_operands", base.SketchTransform.hoistable_operands)
    entry = ENTRY.Entry(CONFIG, CELL_FILE, 1, 1, tiny=True)
    with pytest.raises(RuntimeError, match="hoists no operands"):
        entry.setup()
    assert not hasattr(entry, "X")
