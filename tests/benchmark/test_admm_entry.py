"""The entry kind ``admm_train`` off the chip: its cost functions against
hand counts, what the cell reports, and the rehearsal problem in this
process (x64 off, as the benchmark runs): the program is ``correct``,
both controls and each planted fault are not.

``test_benchmark.py`` rehearses the cell through ``run.py`` in a child
process as it does every cell file; this file holds what is the entry's
own.
"""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)

import run as harness  # noqa: E402 - the harness's own look-up by name

CELL = "admm_mnist8m_hinge"


def entry_module():
    path = os.path.join(BENCH, "entries", "admm_train.py")
    spec = importlib.util.spec_from_file_location("t_admm_train", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ENTRY = entry_module()
MANIFEST, CELL_FILE, CONFIG = harness.load_cell(CELL)


# -- the cost functions, against a hand count -------------------------------


def test_iterate_cost_is_one_feature_pass_and_four_thin_products_an_iteration():
    sizes = {"rows": 6, "d": 5, "targets": 3, "blocks": 2, "block_features": 4}
    flop, nbytes = ENTRY.COSTS["admm_iterate"](sizes, {"iterations": 7})
    D = 2 * 4
    assert flop == 7 * (2 * 6 * 5 * D + 4 * 2 * 6 * D * 3)
    assert nbytes == 7 * 2 * 6 * 5                        # X once an iteration, bf16
    # at the cell's sizes the thin products are 5 % of the feature pass's flop
    z = {**CONFIG, "iterations": 1}
    whole, _ = ENTRY.COSTS["admm_iterate"](z, z)
    feature = 2.0 * z["rows"] * z["d"] * z["blocks"] * z["block_features"]
    assert whole / feature == pytest.approx(1 + 4 * z["targets"] / z["d"])
    assert 0.05 < whole / feature - 1 < 0.052


def test_factor_cost_is_one_feature_pass_and_a_gram_product_a_block():
    sizes = {"rows": 6, "d": 5, "blocks": 2, "block_features": 4}
    flop, nbytes = ENTRY.COSTS["admm_factor"](sizes, {})
    assert flop == 2 * 6 * 5 * 8 + 2 * (2 * 6 * 4 * 4)
    assert nbytes == 2 * 6 * 5


def test_both_programs_are_compute_bound_by_the_chips_peaks():
    peaks = harness.load_json(BENCH, "peaks.json")["TPU v5 lite"]
    for cost in ("admm_iterate", "admm_factor"):
        flop, nbytes = ENTRY.COSTS[cost](CONFIG, {"iterations": CONFIG["maxiter"]})
        assert flop / peaks["bf16_flops_per_s"] > 10 * nbytes / peaks["hbm_bytes_per_s"]
    # a window's iterate program cannot read over 100 %: 1.44 s is its least
    flop, _ = ENTRY.COSTS["admm_iterate"](CONFIG, {"iterations": 20})
    assert flop / peaks["bf16_flops_per_s"] == pytest.approx(1.437, rel=1e-3)


# -- what the cell reports ----------------------------------------------------


def test_the_cell_reports_its_seven_metrics_and_the_shared_ones():
    per_layer = {m["name"] for m in harness.cell_metrics(MANIFEST, "per_layer", CELL)}
    assert per_layer == {
        "admm_iterate_dev_ms", "admm_prepare_dev_ms", "admm_idle_ms", "admm_iters",
        "admm_feature_passes", "admm_iterate_roofline", "admm_factor_roofline",
        "lowerings_per_solve", "unattributed_idle_ms.solve", "device_idle_pct.solve",
        "launches_per_solve"}
    e2e = {m["name"] for m in harness.cell_metrics(MANIFEST, "end_to_end", CELL)}
    assert e2e == {"solve_s", "setup_s"}
    for m in MANIFEST["per_layer"]:
        if m["name"].startswith("admm_"):
            assert m["moves"] == "solve_s" and m["workloads"] == [CELL]
            body = harness.load_json(BENCH, "layer_metrics", m["name"] + ".json")
            assert body["note"] and (m["name"].endswith("roofline") == (m["unit"] == "%"))


def test_the_manifest_entry_and_the_cells_file_agree():
    row = harness.named(MANIFEST["workloads"], CELL)
    assert {k: CELL_FILE[k] for k in row} == row
    assert (row["config"], row["traffic"], row["chips"]) == (
        "admm_mnist8m_bf16", "train_implicit", 1)
    assert CELL_FILE["entry"] == {"kind": "admm_train"}
    assert set(CELL_FILE["limits"]) == set(ENTRY.COMPARED) == set(
        CELL_FILE["limit_reasons"]) - {"readings"}
    crow = harness.named(MANIFEST["configs"], "admm_mnist8m_bf16")
    assert crow["source"] == CONFIG["source"] and len(crow["source"]) <= 200
    assert sorted(crow["reduced"]) == sorted(CONFIG["reduced"])
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1


def test_the_configuration_is_the_source_with_its_cuts_named():
    assert CONFIG["architecture"] is None
    assert (CONFIG["d"], CONFIG["targets"], CONFIG["rows_published"]) == (784, 10, 8100000)
    assert (CONFIG["loss"], CONFIG["regularizer"], CONFIG["kernel"]) == ("hinge", "l2", "gaussian")
    assert (CONFIG["rho"], CONFIG["data_partitions"]) == (1.0, 1)
    assert CONFIG["blocks"] * CONFIG["block_features"] == 4096
    # not cached: the code decides by bytes, and the entry fails a cached call
    assert CONFIG["cache_transforms"] is None and CONFIG["transforms_cached"] == 0
    assert CONFIG["rehearsal"]["cache_transforms"] is False
    assert "rows" in CONFIG["reduced"] and set(CONFIG["reduced"]) <= {"rows", "maxiter"}
    assert CONFIG["maxiter"] == (10 if "maxiter" in CONFIG["reduced"] else 20)
    blocks_gb = 2 * CONFIG["rows"] * 4096 / 1e9
    assert blocks_gb > 16 > 2 * CONFIG["rows"] * CONFIG["block_features"] / 1e9
    for key in ("transforms not cached", "blocks, block_features", "sigma", "lam, maxiter",
                "data", "sketch_seed"):
        assert key in CONFIG["assumed"]
    for key in ("source", "dtype", "deployment"):
        assert CONFIG[key]
    for word in ("skylark_ml", "BlockADMM.hpp:291-560", "1409.0940", "configs[4]"):
        assert word in CONFIG["source"]


# -- the rehearsal problem, in this process -----------------------------------


@pytest.fixture(scope="module")
def rehearsed():
    """One entry at the rehearsal sizes: its answer and both controls'
    (the big seed is the driver's kind)."""
    with jax.enable_x64(False):
        entry = ENTRY.Entry(CONFIG, CELL_FILE, 2**31 + 11, 1, tiny=True)
        entry.setup()
        rec = entry.step()
        return entry, rec, entry.controls()


def compared(entry, answers):
    with jax.enable_x64(False):
        return {name: (value, limit) for name, value, limit in entry.check(answers)}


def test_the_program_is_correct_and_took_the_remade_route(rehearsed):
    entry, rec, _ = rehearsed
    z = entry.sizes
    assert rec["bad"] is None and rec["units"] == {"solutions": 1}
    assert rec["info"] == {"iterations": z["maxiter"], "feature_blocks": z["blocks"],
                           "transforms_cached": 0, "feature_passes": 1}
    D = z["blocks"] * z["block_features"]
    assert rec["answer"].shape == (D * z["targets"] + z["maxiter"],)
    got = compared(entry, [rec["answer"]])
    assert set(got) == set(ENTRY.COMPARED)
    assert all(value <= limit for value, limit in got.values()), got


def test_a_call_that_caches_its_blocks_is_a_failed_step(rehearsed):
    entry, _, _ = rehearsed
    with jax.enable_x64(False):
        entry.sizes["cache_transforms"] = True
        try:
            rec = entry.step()
        finally:
            entry.sizes["cache_transforms"] = False
    assert "transforms_cached 1" in rec["bad"]


@pytest.mark.parametrize("control", ["bf16_state", "fp8_features"])
def test_each_control_is_not_correct(rehearsed, control):
    entry, rec, controls = rehearsed
    got, sound = compared(entry, [controls[control]]), compared(entry, [rec["answer"]])
    assert all(np.isfinite(value) for value, _ in got.values())
    assert any(value > limit for value, limit in got.values()), got
    assert any(got[n][0] > 3 * sound[n][0] for n in got)


def test_control_hands_the_harness_the_nearer_of_the_two(rehearsed):
    entry, _, controls = rehearsed
    with jax.enable_x64(False):
        chosen = entry.control()
    worst = {name: max(v / lim for v, lim in compared(entry, [a]).values())
             for name, a in controls.items()}
    nearer = min(worst, key=worst.get)
    assert np.array_equal(np.asarray(chosen), np.asarray(controls[nearer]))
    assert worst[nearer] > 1


@pytest.fixture
def fresh_programs():
    """A fault planted in the library's step needs programs traced anew,
    and leaves none of its own behind."""
    from libskylark_tpu.ml import admm

    def clear():
        for program in (admm.admm_iterate, admm.admm_factor):
            program.clear_cache()

    clear()
    yield admm
    clear()


@pytest.mark.parametrize("fault", ["iteration_dropped", "block_solve_skipped", "half_left_out"])
def test_a_planted_fault_is_not_correct(rehearsed, fresh_programs, monkeypatch, fault):
    entry, rec, _ = rehearsed
    z, admm = entry.sizes, fresh_programs
    D, k = z["blocks"] * z["block_features"], z["targets"]
    with jax.enable_x64(False):
        if fault == "iteration_dropped":   # one iteration fewer, the trace filled up
            monkeypatch.setitem(z, "maxiter", z["maxiter"] - 1)
            W, objs = ENTRY.unpack(entry.step()["answer"], D, k)
            broken = ENTRY.pack(W, jnp.concatenate([objs, objs[-1:]]))
        elif fault == "block_solve_skipped":  # block 1 takes its right-hand side for the solve
            calls, real = [], admm._chol_solve

            def skipping(L, B):
                calls.append(1)
                return B if len(calls) % z["blocks"] == 2 else real(L, B)

            monkeypatch.setattr(admm, "_chol_solve", skipping)
            broken = entry.step()["answer"]
            assert calls
        else:                              # a fit on the first half of the rows
            X, Y = entry.X, entry.Y
            entry.X, entry.Y = X[: z["rows"] // 2], Y[: z["rows"] // 2]
            try:
                broken = entry.step()["answer"]
            finally:
                entry.X, entry.Y = X, Y
    assert broken.shape == rec["answer"].shape
    got = compared(entry, [broken])
    assert any(value > limit for value, limit in got.values()), got
    # the worst answer of a window decides
    assert compared(entry, [rec["answer"], broken]) == got


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "entries", "admm_train.py")) as fh:
        source = fh.read()
    head = source.split("# -- the entry")[0]
    assert "libskylark_tpu" not in head.split('"""', 2)[2]
    assert source.count("from libskylark_tpu import") == 2  # set-up and the step alone
