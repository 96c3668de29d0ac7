"""The entry kind ``krr_ppt_sparse`` off the chip: its manifest rows
against its files, the configuration as published, the sparse
CountSketch's cost against a hand count, and the rehearsal problem in
this process (x64 off, as the benchmark runs): the program is
``correct``; the fp8 control and the reference with one level dropped
are not; a program without the row panels is refused before any data
are made.

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

CELL = "krr_poly2_url_sparse_resident"


def entry_module():
    path = os.path.join(BENCH, "entries", "krr_ppt_sparse.py")
    spec = importlib.util.spec_from_file_location("t_krr_ppt_sparse_entry", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ENTRY = entry_module()
MANIFEST, CELL_FILE, CONFIG = harness.load_cell(CELL)


# -- the manifest rows and the files ------------------------------------------


def test_the_manifest_entries_and_the_cells_files_agree():
    row = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert row == {k: CELL_FILE[k] for k in ("name", "config", "traffic", "chips", "why")}
    assert row["chips"] == 1 and CELL_FILE["entry"] == {"kind": "krr_ppt_sparse"}
    conf = next(c for c in MANIFEST["configs"] if c["name"] == CELL_FILE["config"])
    assert conf["source"] == CONFIG["source"] and conf["reduced"] == list(CONFIG["reduced"])
    assert conf["file"] == f"benchmarks/configs/{CONFIG['name']}.json"
    assert len(conf["why"]) <= 200 and len(conf["source"]) <= 200 and len(row["why"]) <= 200
    e2e = {m["name"] for m in harness.cell_metrics(MANIFEST, "end_to_end", CELL)}
    assert e2e == {"train_rows_per_s", "setup_s"}
    per_layer = {m["name"] for m in harness.cell_metrics(MANIFEST, "per_layer", CELL)}
    assert per_layer == {
        "device_idle_pct.train", "sweep_ms", "program_build_idle_ms", "lowerings_per_call",
        "unattributed_idle_ms.train", "program_temp_bytes.train", "recorded_dev_pct.train",
        "krr_feature_pass_dev_ms", "krr_gram_product_dev_ms",
        "ppt_feature_dev_ms", "ppt_dft_dev_ms", "ppt_hash_dev_ms", "krr_feature_passes",
        "ppt_roofline", "ppt_scatter_dev_ms", "ppt_scatter_roofline"}


def test_the_configuration_is_urls_published_counts_uncut():
    assert CONFIG["architecture"] is None and CONFIG["kernel"] == "polynomial"
    assert (CONFIG["rows"], CONFIG["d"], CONFIG["nnz"]) == (2_396_130, 3_231_961, 277_058_644)
    assert (CONFIG["targets"], CONFIG["s"], CONFIG["q"], CONFIG["c"]) == (2, 4096, 2, 1.0)
    assert CONFIG["gamma"] == 1.0 / 115.6 and CONFIG["sweeps"] == 2
    assert CONFIG["reduced"] == {}
    assert CONFIG["rows"] % CONFIG["block_rows"] == 0 and CONFIG["block_rows"] >= 4096
    assert CONFIG["s"] * CONFIG["block_rows"] <= 1 << 28  # the dense-output limit
    assert CONFIG["rows"] % CONFIG["ref_block"] == 0
    for key in ("q, c", "s", "gamma, lam", "sweeps", "targets", "nonzeros a row", "columns",
                "labels", "block_rows", "sketch_seed"):
        assert key in CONFIG["assumed"]
    assert set(CELL_FILE["limits"]) == {"pred_rel_err"} <= set(CELL_FILE["limit_reasons"])


def test_row_lengths_add_up_to_the_published_count_within_their_spread():
    rng = np.random.default_rng(2**31 + 5)
    n = ENTRY.row_lengths(rng, CONFIG["rows"], CONFIG["nnz"], CONFIG["real_cols"],
                          CONFIG["row_sigma"])
    assert n.sum() == CONFIG["nnz"] and n.shape == (CONFIG["rows"],)
    assert n.min() > CONFIG["real_cols"] and 800 < n.max() < 1600
    assert 100 < np.median(n) < CONFIG["nnz"] / CONFIG["rows"]  # right-skewed


def test_the_panels_slots_at_the_cells_counts():
    """The layout the cell runs: 128 slots a row in place, the rest of
    the longer rows in overflow rows, about 1.5 slots a nonzero."""
    from libskylark_tpu.core import sparse

    rng = np.random.default_rng(2**31 + 5)
    n = ENTRY.row_lengths(rng, CONFIG["rows"], CONFIG["nnz"], CONFIG["real_cols"],
                          CONFIG["row_sigma"])
    k, e = sparse._slot_width(n, CONFIG["block_rows"])
    assert k == 128 and 14_000 < e < 17_000
    slots = CONFIG["rows"] // CONFIG["block_rows"] * (CONFIG["block_rows"] + e) * k
    assert 1.45 < slots / CONFIG["nnz"] < 1.52


# -- the cost function, against a hand count -----------------------------------


def test_the_scatter_cost_counts_every_nonzero_fed_and_the_bins():
    # 7 nonzeros, 2 levels, 5 passes: 70 fed; 3 rows of 8 bins, f32, a level and pass
    flop, nbytes = ENTRY.COSTS["ppt_scatter"](
        {"rows": 3, "s": 8, "q": 2}, {"feature_nnz": 70, "feature_passes": 5})
    assert flop == 2 * 70
    assert nbytes == 70 * (10 + 8) + 2 * 5 * 3 * 8 * 4
    # at the cell: about 0.54 s a call at 819 GB/s, memory-bound
    info = {"feature_nnz": CONFIG["nnz"] * 2 * 5, "feature_passes": 5}
    flop, nbytes = ENTRY.COSTS["ppt_scatter"](CONFIG, info)
    peaks = harness.load_json(BENCH, "peaks.json")["TPU v5 lite"]
    assert nbytes / peaks["hbm_bytes_per_s"] > flop / peaks["bf16_flops_per_s"]
    assert 0.5 < nbytes / peaks["hbm_bytes_per_s"] < 0.6


def test_the_feature_cost_counts_the_hash_on_the_nonzeros():
    flop, nbytes = ENTRY.COSTS["ppt_features"](
        {"rows": 10, "nnz": 7, "s": 8, "q": 2}, {"feature_passes": 5})
    # a row: 3 * 2.5 * 8 * 3 = 180, 6 * 1 * 5 = 30; the hash 2 q nnz = 28 a pass
    assert flop == 5 * (10 * (180 + 30) + 28)
    assert nbytes == 5 * 7 * 10


# -- the rehearsal problem, in this process ------------------------------------


@pytest.fixture(scope="module")
def rehearsed():
    """One entry at the rehearsal sizes, its answer and the control's
    (a seed over 2**31, as the benchmark's runs draw)."""
    with jax.enable_x64(False):
        entry = ENTRY.Entry(CONFIG, CELL_FILE, 2**31 + 23, 1, tiny=True)
        entry.setup()
        rec = entry.step()
        entry.release()
        return entry, rec, entry.control()


def compared(entry, answers):
    with jax.enable_x64(False):
        return {name: (value, limit) for name, value, limit in entry.check(answers)}


def test_the_rehearsal_is_correct_and_reports_its_nonzeros(rehearsed):
    entry, rec, _ = rehearsed
    z = entry.sizes
    assert entry.data.shape == (z["nnz"],) and entry.indices.shape == (z["nnz"], 2)
    assert isinstance(entry.data, np.ndarray) and isinstance(entry.indices, np.ndarray)
    assert rec["bad"] is None
    assert rec["info"] == {"feature_passes": 5, "feature_map": "PPT",
                           "feature_nnz": z["nnz"] * 2 * 5}
    assert rec["units"] == {"rows": 2 * z["rows"]}
    assert rec["answer"].shape == (z["s"], 2)
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


def test_the_data_are_distinct_sorted_columns_a_row(rehearsed):
    entry, _, _ = rehearsed
    z = entry.sizes
    ind = entry.indices
    assert (np.diff(ind[:, 0]) >= 0).all() and ind[:, 1].max() < z["d"]
    same_row = np.diff(ind[:, 0]) == 0
    assert (np.diff(ind[:, 1])[same_row] > 0).all()  # no repeats within a row
    data = np.asarray(entry.data, np.float32)
    real = ind[:, 1] < z["real_cols"]
    assert (data[~real] == 1.0).all() and ((data[real] > 0) & (data[real] <= 1)).all()
    assert (np.asarray(entry.Y) ** 2 == 1).all()


def test_a_program_without_panels_is_refused_at_setup(monkeypatch):
    """A program that has no row panels (an older one) is refused at
    once, before any data or compilation."""
    from libskylark_tpu.core import sparse

    monkeypatch.delattr(sparse, "panels")
    entry = ENTRY.Entry(CONFIG, CELL_FILE, 1, 1, tiny=True)
    with pytest.raises(RuntimeError, match="no core.sparse.panels"):
        entry.setup()
    assert not hasattr(entry, "data")
