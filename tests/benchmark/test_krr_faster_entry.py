"""The entry kind ``krr_faster`` off the chip: its cost functions against
hand counts, what the cell reports, and the rehearsal problem in this
process (x64 off, as the benchmark runs): the program is ``correct``,
the bfloat16 control and each planted fault are not, and every seed is
the same system in another order of rows.

``test_benchmark.py`` rehearses the cell through ``run.py`` in a child
process as it does every cell file; this file holds what is the entry's
own.
"""

import importlib.util
import json
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

CELL = "krr_faster_mnist_pcg"


def entry_module():
    path = os.path.join(BENCH, "entries", "krr_faster.py")
    spec = importlib.util.spec_from_file_location("t_krr_faster_entry", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ENTRY = entry_module()
MANIFEST, CELL_FILE, CONFIG = harness.load_cell(CELL)


# -- the cost functions, against a hand count -------------------------------


def test_gram_cost_is_the_cross_term_and_one_write_of_k():
    flop, nbytes = ENTRY.COSTS["gram"]({"rows": 6, "d": 5}, {})
    assert flop == 2 * 6 * 6 * 5                  # a multiply and an add a pair and column
    assert nbytes == 4 * (6 * 6 + 6 * 5)          # K out, X in, f32


def test_pcg_cost_reads_the_lower_triangle_and_the_factor_twice():
    sizes = {"rows": 6, "s": 4, "targets": 3}
    flop, nbytes = ENTRY.COSTS["pcg"](sizes, {"cg_iters": 7})
    assert nbytes == 7 * 4 * (6 * 7 // 2 + 2 * 4 * 6)   # 21 entries of K, U~ twice, f32
    assert flop == 7 * (2 * 6 * 6 * 3 + 2 * 2 * 4 * 6 * 3)
    # all of K is 36 entries: a segment that reads it whole shows at most
    # (21 + 48) / (36 + 48) of this roofline, 57 % at the cell's sizes
    z = {**CONFIG, "cg_iters": 1}
    _, least = ENTRY.COSTS["pcg"](z, z)
    whole = 4.0 * (z["rows"] ** 2 + 2 * z["s"] * z["rows"])
    assert 0.55 < least / whole < 0.58


def test_the_roofline_of_an_f32_gram_product_tops_out_at_a_sixth():
    """``gram_roofline`` is flop over the bf16 peak: six bf16 passes an
    f32 product at ``highest`` leave 16.7 % as the ceiling today."""
    peaks = harness.load_json(BENCH, "peaks.json")["TPU v5 lite"]
    flop, nbytes = ENTRY.COSTS["gram"](CONFIG, {})
    assert flop / peaks["bf16_flops_per_s"] > nbytes / peaks["hbm_bytes_per_s"]  # compute-bound
    assert 6 * flop == pytest.approx(6 * 2 * 49152**2 * 784)


# -- what the cell reports ----------------------------------------------------


def test_the_cell_reports_its_six_metrics_and_the_solver_layers():
    per_layer = {m["name"] for m in harness.cell_metrics(MANIFEST, "per_layer", CELL)}
    assert {"gram_dev_ms", "gram_roofline", "precond_dev_ms", "cg_iters", "cg_roofline",
            "faster_krr_idle_ms", "krylov_dev_ms", "segment_build_idle_ms",
            "lowerings_per_solve", "unattributed_idle_ms.solve", "device_idle_pct.solve",
            "launches_per_solve"} <= per_layer
    assert "lsqr_iters" not in per_layer and "krr_roofline" not in per_layer
    e2e = {m["name"] for m in harness.cell_metrics(MANIFEST, "end_to_end", CELL)}
    assert e2e == {"solve_s", "setup_s"}


def test_the_configuration_is_the_source_with_one_cut():
    assert CONFIG["architecture"] is None
    assert (CONFIG["d"], CONFIG["targets"], CONFIG["s"]) == (784, 10, 4096)
    assert (CONFIG["lam"], CONFIG["tolerance"], CONFIG["iter_lim"]) == (0.01, 1e-3, 1000)
    assert list(CONFIG["reduced"]) == ["rows"] and CONFIG["rows_published"] == 60000
    assert 4 * CONFIG["rows"] ** 2 < 10e9 < 4 * CONFIG["rows_published"] ** 2
    for key in ("s", "sigma", "data", "seed", "sketch_seed"):
        assert key in CONFIG["assumed"]
    assert set(CELL_FILE["limits"]) == set(ENTRY.COMPARED) == set(CELL_FILE["limit_reasons"]) - {
        "readings"}


# -- the rehearsal problem, in this process -----------------------------------


@pytest.fixture(scope="module")
def rehearsed():
    """One entry at the rehearsal sizes: its answer, the reference's
    parts and the control's answer (the big seed is the driver's kind)."""
    with jax.enable_x64(False):
        entry = ENTRY.Entry(CONFIG, CELL_FILE, 2**31 + 11, 1, tiny=True)
        entry.setup()
        rec = entry.step()
        return entry, rec, entry.control()


def compared(entry, answers):
    with jax.enable_x64(False):
        return {name: (value, limit) for name, value, limit in entry.check(answers)}


def test_the_program_is_correct_and_reports_its_iterations(rehearsed):
    entry, rec, _ = rehearsed
    assert rec["bad"] is None and rec["units"] == {"solutions": 1}
    assert 10 <= rec["info"]["cg_iters"] <= 40
    assert rec["answer"].shape == (entry.sizes["rows"], 10)
    got = compared(entry, [rec["answer"]])
    assert all(value <= limit for value, limit in got.values()), got


def test_the_bfloat16_control_is_not_correct_by_either_limit(rehearsed):
    entry, rec, control = rehearsed
    got, sound = compared(entry, [control]), compared(entry, [rec["answer"]])
    assert all(np.isfinite(value) for value, _ in got.values())
    assert all(value > 10 * limit for value, limit in got.values()), got
    assert all(got[n][0] > 100 * sound[n][0] for n in got)


@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out", "one_class_unfit"])
def test_a_planted_fault_is_not_correct(rehearsed, fault):
    entry, rec, _ = rehearsed
    alpha = rec["answer"]
    with jax.enable_x64(False):
        if fault == "answer_altered":      # one entry moved by the answer's own norm
            broken = alpha.at[0, 0].add(jnp.linalg.norm(alpha))
        elif fault == "half_left_out":     # a fit on the first half of the rows
            z, X, Y = entry.sizes, entry.X, entry.Y
            entry.X, entry.Y = X[: z["rows"] // 2], Y[: z["rows"] // 2]
            try:
                broken = entry.step()["answer"]
            finally:
                entry.X, entry.Y = X, Y
            assert broken.shape[0] == z["rows"] // 2
        else:                              # a right-hand side never solved for
            broken = alpha.at[:, 3].set(0.0)
    got = compared(entry, [broken])
    assert any(value > limit for value, limit in got.values()), got
    # the worst answer of a window decides
    assert compared(entry, [alpha, broken]) == got


def test_every_seed_is_the_same_system_in_another_order():
    """``--seed`` orders the rows: the same multiset of rows and labels,
    so K, the features and the codes are permuted alike."""
    with jax.enable_x64(False):
        z = {**CONFIG, **CONFIG["rehearsal"]}
        Xa, ya = ENTRY.make_data(1, z["data_seed"], z)
        Xb, yb = ENTRY.make_data(2**31 + 5, z["data_seed"], z)
        again = ENTRY.make_data(1, z["data_seed"], z)
    assert np.array_equal(np.asarray(Xa), np.asarray(again[0]))
    assert not np.array_equal(np.asarray(Xa), np.asarray(Xb))
    rows = lambda X, y: sorted(map(bytes, np.asarray(jnp.column_stack([X, y]))))  # noqa: E731
    assert rows(Xa, ya) == rows(Xb, yb)
    assert set(np.asarray(ya).tolist()) == set(range(z["targets"]))
    # the median rule the configuration states: |x - y|^2 / 2 sigma^2 about 1
    d2 = jnp.sum((Xa[:256, None, :] - Xa[None, :256, :]) ** 2, -1) / (2 * z["sigma"] ** 2)
    assert 0.6 < float(jnp.median(d2)) < 1.4


def test_the_reference_does_not_rest_on_the_draws_it_reads(rehearsed):
    """The reference reads the feature map's W and shifts from a map
    built like the program's.  They shape only its preconditioner: CG to
    1e-6 on K_ref without any (U~ = 0 makes M = I / lam) gives the same
    predictions, and the program reads the same against either."""
    entry, rec, _ = rehearsed
    z = entry.sizes
    with jax.enable_x64(False):
        K, Y, alpha_ref = entry.reference()
        lam = jnp.float32(z["lam"])
        none = jnp.zeros((z["s"], z["rows"]), jnp.float32)
        plain = ENTRY.reference_pcg(K, none, Y, jnp.zeros_like(Y), lam, z["ref_block"])
        plain = ENTRY.reference_pcg(K, none, Y, plain, lam, z["ref_block"])
        idx = jnp.arange(z["sample_rows"])
        preds = [ENTRY.sampled_predictions(K, a, idx) for a in (alpha_ref, plain)]
        apart = float(jnp.linalg.norm(preds[0] - preds[1]) / jnp.linalg.norm(preds[0]))
        read = [float(ENTRY.compare(K, Y, lam, idx, p, rec["answer"])[1]) for p in preds]
    assert apart < 2e-5, apart
    assert abs(read[0] - read[1]) < 0.02 * read[0], read
