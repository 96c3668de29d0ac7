"""The entry kind ``graph_se`` off the chip: its cost function against a
hand count, what the cell reports, the graph it draws, and the rehearsal
problem in this process (x64 off, as the benchmark runs): the program is
``correct``, the bfloat16 control and each planted fault are not.

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

CELL = "graph_se_orkut_k8"


def entry_module():
    path = os.path.join(BENCH, "entries", "graph_se.py")
    spec = importlib.util.spec_from_file_location("t_graph_se_entry", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ENTRY = entry_module()
MANIFEST, CELL_FILE, CONFIG = harness.load_cell(CELL)


# -- the cost function, against a hand count ----------------------------------


def test_product_cost_reads_every_nonzero_and_one_panel_row_for_it():
    sizes = {"vertices": 10, "nnz": 30, "s": 4}
    flop, nbytes = ENTRY.COSTS["ase_product_cost"](sizes, {"products": 6})
    # a nonzero: two int32 indices, an f32 value, one 4-column f32 row; the result
    assert nbytes == 6 * (30 * (8 + 4 + 16) + 10 * 16)
    assert flop == 6 * 2 * 30 * 4
    assert ENTRY.COSTS["ase_product_cost"](sizes, {"products": 2})[1] == nbytes / 3


def test_the_products_are_memory_bound_and_cannot_read_over_the_roofline():
    peaks = harness.load_json(BENCH, "peaks.json")["TPU v5 lite"]
    z = {**CONFIG, "nnz": 2 * CONFIG["edges"]}
    flop, nbytes = ENTRY.COSTS["ase_product_cost"](z, {"products": 6})
    least = nbytes / peaks["hbm_bytes_per_s"]
    assert least > 50 * flop / peaks["bf16_flops_per_s"]
    # six products at the source's size: 76 bytes a nonzero, 64 a vertex
    per_product = 2 * CONFIG["edges"] * 76 + CONFIG["vertices"] * 64
    assert nbytes == 6 * per_product
    if not CONFIG["reduced"]:
        assert per_product == pytest.approx(18.0e9, rel=5e-3)   # ISSUE 37's reckoning


# -- what the cell reports ----------------------------------------------------


def test_the_cell_reports_its_own_metrics_and_the_shared_ones():
    """The orthonormalization and the sweep segment are ``linalg/svd.py``'s
    ``gram_orth`` and ``_chunk``, the four-chip SVD cell's: both cells
    report them under the accepted names (ISSUE 37's ``ase_orth_dev_ms``
    would have read the same scope in the same module)."""
    per_layer = {m["name"] for m in harness.cell_metrics(MANIFEST, "per_layer", CELL)}
    assert per_layer == {
        "ase_product_dev_ms", "ase_product_roofline",
        "ase_stage_idle_ms", "ase_products",
        "svd_power_dev_ms", "svd_gram_orth_dev_ms", "svd_sweep_products_dev_ms",
        "lowerings_per_solve", "unattributed_idle_ms.solve", "device_idle_pct.solve",
        "launches_per_solve", "program_temp_bytes.solve", "recorded_dev_pct.solve"}
    e2e = {m["name"] for m in harness.cell_metrics(MANIFEST, "end_to_end", CELL)}
    assert e2e == {"solve_s", "setup_s"}
    for m in MANIFEST["per_layer"]:
        if m["name"].startswith("ase_"):
            assert m["moves"] == "solve_s" and m["workloads"] == [CELL]
            assert m["name"].endswith("roofline") == (m["unit"] == "%")
        if m["name"].startswith("svd_") and CELL in m["workloads"]:
            assert m["workloads"] == ["svd_rand_1e7_k100_x4", CELL]
            reader = harness.load_json(BENCH, "layer_metrics", m["name"] + ".json")["reader"]
            assert reader["module"] == "^jit__chunk$"


def test_the_manifest_entry_and_the_cells_file_agree():
    row = harness.named(MANIFEST["workloads"], CELL)
    assert {k: CELL_FILE[k] for k in row} == row
    assert (row["config"], row["traffic"], row["chips"]) == (
        "graph_se_orkut_f32", "embed_rank8", 1)
    assert CELL_FILE["entry"] == {"kind": "graph_se"}
    assert set(CELL_FILE["limits"]) == set(ENTRY.COMPARED) == set(
        CELL_FILE["limit_reasons"]) - {"readings"}
    crow = harness.named(MANIFEST["configs"], "graph_se_orkut_f32")
    assert crow["source"] == CONFIG["source"] and len(crow["source"]) <= 200
    assert sorted(crow["reduced"]) == sorted(CONFIG["reduced"])
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    assert MANIFEST["workloads"][-1] == row and MANIFEST["configs"][-1] == crow


def test_the_configuration_is_the_source_with_its_cuts_named():
    assert CONFIG["architecture"] is None
    assert CONFIG["published"]["vertices, edges"].count("3,072,441") == 1
    assert (CONFIG["rank"], CONFIG["num_iterations"], CONFIG["oversampling_ratio"],
            CONFIG["s"], CONFIG["skip_qr"], CONFIG["sparse"]) == (8, 2, 2, 16, False, True)
    assert (CONFIG["mean_degree"], CONFIG["max_degree"]) == (76.28, 33313)
    # vertices and edges are cut by one factor or not at all; nothing else is
    assert set(CONFIG["reduced"]) in (set(), {"vertices", "edges"})
    assert CONFIG["edges"] / CONFIG["vertices"] == pytest.approx(76.28 / 2, rel=1e-3)
    if not CONFIG["reduced"]:
        assert (CONFIG["vertices"], CONFIG["edges"]) == (3072441, 117185083)
    # never under the 0.6 that ISSUE 37 gives as the least, and the columns
    # in more than one table, as at the source's size
    from libskylark_tpu.core import sparse

    assert CONFIG["vertices"] >= 0.6 * 3072441 - 1
    assert CONFIG["vertices"] > sparse._table(CONFIG["vertices"])
    assert sparse._table(CONFIG["vertices"]) <= sparse.TABLE_ROWS
    assert abs(sum(CONFIG["community_sizes"]) - 1) < 1e-9 and len(CONFIG["community_sizes"]) == 8
    for key in ("the graph", "degree_exponent", "community_sizes, share_power", "mixing",
                "arcs_drawn", "sketch_seed"):
        assert key in CONFIG["assumed"]
    for word in ("skylark_graph_se.cpp", "spectral_embedding.hpp:19-94", "svd.hpp:321-392",
                 "com-Orkut", "3,072,441", "117,185,083"):
        assert word in CONFIG["source"]


# -- the graph ----------------------------------------------------------------


def test_the_rank_law_has_the_largest_degree_over_the_mean_asked_for():
    n, tau, ratio = 100_000, 2.5, 300.0
    r0 = ENTRY.rank_offset(n, tau, ratio)
    w = (np.arange(n) + 0.5 + r0) ** (-1 / (tau - 1))
    assert r0 ** (-1 / (tau - 1)) / w.mean() == pytest.approx(ratio, rel=1e-3)


def test_every_seed_draws_the_same_graph_with_other_labels():
    with jax.enable_x64(False):
        z = {**CONFIG, **CONFIG["rehearsal"]}
        first, sizes, edge, r0 = ENTRY.communities(z)
        assert sizes.sum() == z["vertices"] and first[0] == 0
        assert abs(edge.sum() - 1) < 1e-12 and (np.diff(edge / sizes) > 0).all()  # smaller, denser
        arcs = [ENTRY.make_arcs(z, seed) for seed in (7, 2**31 + 11)]
        n = z["vertices"]
        degrees = []
        for u, v in arcs:
            assert u.shape == v.shape == (z["arcs_drawn"],) and u.dtype == jnp.int32
            assert int(u.min()) >= 0 and int(jnp.maximum(u, v).max()) < n
            rows, cols, window = ENTRY.adjacency(u, v, n, z["edge_block"])
            live = np.asarray(rows) < n
            degrees.append(np.sort(np.bincount(np.asarray(rows)[live], minlength=n)))
        assert not np.array_equal(np.asarray(arcs[0][0]), np.asarray(arcs[1][0]))
        np.testing.assert_array_equal(degrees[0], degrees[1])   # a relabelling
        assert degrees[0][-1] > 10 * degrees[0].mean()          # a heavy tail


def test_the_references_adjacency_is_symmetric_simple_and_sorted():
    u = jnp.asarray([3, 1, 1, 2, 2, 0, 3], jnp.int32)
    v = jnp.asarray([1, 3, 1, 0, 0, 2, 2], jnp.int32)    # 3-1 twice, 1-1, 2-0 thrice, 3-2
    rows, cols, window = ENTRY.adjacency(u, v, 4, 8)
    np.testing.assert_array_equal(rows, [0, 1, 2, 2, 3, 3, 4, 4])
    np.testing.assert_array_equal(cols, [2, 3, 0, 3, 1, 2, 4, 4])
    assert window == 4
    Y = jnp.arange(8, dtype=jnp.float32).reshape(4, 2)
    A = np.zeros((4, 4), np.float32)
    A[np.asarray(rows)[:6], np.asarray(cols)[:6]] = 1
    np.testing.assert_array_equal(
        ENTRY.product(rows, cols, Y, block=8, window=window), A @ np.asarray(Y))


# -- the rehearsal problem, in this process -----------------------------------


@pytest.fixture(scope="module")
def rehearsed():
    """One entry at the rehearsal sizes: its answer and the control's
    (the big seed is the driver's kind)."""
    with jax.enable_x64(False):
        entry = ENTRY.Entry(CONFIG, CELL_FILE, 2**31 + 11, 1, tiny=True)
        entry.setup()
        rec = entry.step()
        held = entry.A
        entry.release()
        control = entry.control()
        return entry, rec, control, held


def compared(entry, answers):
    with jax.enable_x64(False):
        return {name: (value, limit) for name, value, limit in entry.check(answers)}


def test_the_program_is_correct_and_ran_six_products(rehearsed):
    entry, rec, _, _ = rehearsed
    z = entry.sizes
    assert rec["bad"] is None and rec["units"] == {"solutions": 1}
    assert rec["info"] == {"products": 6, "iterations": 2, "nnz": z["nnz"],
                           "edge_chunks": 1}
    assert z["rows"] == z["nnz"] and z["nnz"] % 2 == 0
    assert rec["answer"].shape == (1 + z["vertices"], z["rank"])
    got = compared(entry, [rec["answer"]])
    assert set(got) == set(ENTRY.COMPARED)
    assert all(value <= limit for value, limit in got.values()), got
    # the eight eigenvalues compared stand clear of the ninth Ritz value
    ritz = np.abs(np.asarray(entry.ritz_values))
    assert ritz[7] > 1.5 * ritz[8] and (np.diff(ritz[:8]) < 0).all()


def test_the_control_in_bfloat16_is_not_correct(rehearsed):
    entry, rec, control, _ = rehearsed
    got, sound = compared(entry, [control]), compared(entry, [rec["answer"]])
    assert all(np.isfinite(value) for value, _ in got.values())
    assert any(value > limit for value, limit in got.values()), got
    assert any(got[n][0] > 3 * sound[n][0] for n in got)


@pytest.mark.parametrize("fault", ["a_sweep_dropped", "half_left_out", "a_product_in_bfloat16"])
def test_a_planted_fault_is_not_correct(rehearsed, monkeypatch, fault):
    entry, rec, _, held = rehearsed
    z = entry.sizes
    with jax.enable_x64(False):
        entry.A = held
        try:
            if fault == "a_sweep_dropped":
                monkeypatch.setitem(z, "num_iterations", 1)
            elif fault == "half_left_out":      # as the harness's own fault halves it
                entry.A = held[: z["rows"] // 2]
            else:                               # the panel rounded before every product
                from libskylark_tpu.core import sparse
                from libskylark_tpu.linalg import svd

                real = sparse.spmm
                monkeypatch.setattr(svd, "spmm", lambda A, Y, **kw: real(
                    A, Y.astype(jnp.bfloat16).astype(jnp.float32), **kw))
                for program in (svd._chunk, svd._ritz):
                    program.clear_cache()
            broken = entry.step()["answer"]
        finally:
            entry.release()
            if fault == "a_product_in_bfloat16":
                monkeypatch.undo()
                for program in (svd._chunk, svd._ritz):
                    program.clear_cache()
    assert broken.shape == rec["answer"].shape
    got = compared(entry, [broken])
    assert any(value > limit for value, limit in got.values()), got
    # the worst answer of a window decides
    assert compared(entry, [rec["answer"], broken]) == got


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "entries", "graph_se.py")) as fh:
        source = fh.read()
    head = source.split("# -- the entry")[0]
    assert "libskylark_tpu" not in head.split('"""', 2)[2]
    # set-up, the operand, the step and the draws alone
    assert source.count("from libskylark_tpu") == 6
