"""The adjacency spectral embedding's stage spans, read back from a
profiler trace on the CPU: ``ase.sketch``, ``ase.power``, ``ase.ritz``,
``ase.embed`` open once a call and in that order inside the entry span
``approximate_ase``, for a dense adjacency and for a prepared sparse one;
``info["products"]`` counts the products with A.

A file of its own: a process has one profiler session at a time, and the
suite gives a file to one worker (``tests/test_stage_spans.py`` holds the
solvers' and the trainer's).
"""

import numpy as np
import pytest
from test_stage_spans import under_profiler  # the suite's one trace-and-read helper

from libskylark_tpu import SketchContext
from libskylark_tpu.core import sparse
from libskylark_tpu.core.sparse import prepare
from libskylark_tpu.graph import ASEParams, adjacency_from_edges, approximate_ase

pytestmark = pytest.mark.telemetry

STAGES = ["ase.sketch", "ase.power", "ase.ritz", "ase.embed"]


def operand(form):
    rng = np.random.default_rng(21)
    u, v = rng.integers(0, 400, 6000), rng.integers(0, 400, 6000)
    if form == "hot_table":  # 32 hubs, and fewer columns in the hot table than vertices
        u, v = np.append(u, rng.integers(0, 32, 9000)), np.append(v, rng.integers(0, 400, 9000))
    A = adjacency_from_edges(u, v, 400)
    with pytest.MonkeyPatch.context() as patch:
        if form == "hot_table":
            patch.setattr(sparse, "HOT_ROWS", 48)
        return {"dense": A.todense, "bcoo": lambda: A,
                "prepared": lambda: prepare(A, symmetric=True),
                "hot_table": lambda: prepare(A, symmetric=True)}[form]()


def embed(A, q):
    return approximate_ase(A, 4, SketchContext(seed=13),
                           ASEParams(num_iterations=q, sparse=True), return_info=True)


@pytest.mark.parametrize("form", ["dense", "prepared", "hot_table"])
def test_the_four_stage_spans_open_once_a_call_in_order(form, tmp_path):
    A = operand(form)
    plain = embed(A, 2)
    under, spans = under_profiler(lambda: embed(A, 2), tmp_path)
    spans = sorted(spans, key=lambda span: span[1])
    assert [name for name, _, _ in spans] == ["approximate_ase", *STAGES]
    (_, lo, hi), stages = spans[0], spans[1:]
    assert all(lo <= s and e <= hi for _, s, e in stages)
    assert all(e <= s for (_, _, e), (_, s, _) in zip(stages, stages[1:]))
    # under a profiler session the answer is the same to the bit
    for a, b in zip(plain[0], under[0]):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert plain[1] == under[1]


@pytest.mark.parametrize("q,products", [(0, 2), (1, 4), (2, 6), (3, 8)])
def test_info_counts_the_products_with_the_adjacency(q, products):
    A = operand("prepared")
    (X, lam), info = embed(A, q)
    assert info["products"] == products == 2 + 2 * q
    assert info["iterations"] == q and info["nnz"] == A.nse
    assert info["edge_chunks"] == 1 and X.shape == (400, 4) and lam.shape == (4,)
    (_, _), dense = embed(operand("dense"), q)
    assert dense == {"products": products, "iterations": q, "nnz": 400 * 400,
                     "edge_chunks": 0}
    # an operand with a hot table says so; any other's info is what it was
    assert set(info) == set(dense) and (A.tables, A.hot_share) == (1, 0.0)
    H = operand("hot_table")
    (_, _), hot = embed(H, q)
    assert {k: hot[k] for k in dense} == {**info, "nnz": H.nse, "edge_chunks": 2}
    assert hot["tables"] == H.tables == 2 and H.hot.shape == (48,)
    assert hot["hot_share"] == H.hot_share == H.hot_nse / H.nse > 0.3
