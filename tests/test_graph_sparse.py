"""The sparse route of ``skylark_graph_se``: the adjacency built on the
device from integer edge arrays, ``approximate_ase`` on it against the
benchmark entry's plain reference and against the dense route, the dense
route against the recurrence as it stood op by op before it became three
cached programs, a warm call that builds nothing, and the CLI.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _builds import builds

from libskylark_tpu import SketchContext
from libskylark_tpu.core import sparse
from libskylark_tpu.core.sparse import Prepared, prepare
from libskylark_tpu.graph import (
    ASEParams, SimpleGraph, adjacency_from_edges, approximate_ase)
from libskylark_tpu.linalg import SVDParams, approximate_symmetric_svd, svd
from libskylark_tpu.sketch import JLT

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def entry_module():
    path = os.path.join(REPO, "benchmarks", "entries", "graph_se.py")
    spec = importlib.util.spec_from_file_location("t_graph_se", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ENTRY = entry_module()
PLANTED = {  # a seeded degree-corrected planted partition, a few thousand vertices
    "vertices": 3000, "community_sizes": [0.3, 0.25, 0.2, 0.15, 0.1], "share_power": 0.7,
    "degree_exponent": 2.5, "max_degree": 700, "mean_degree": 40.0, "mixing": 0.15,
    "arcs_drawn": 62000, "block": 4096, "data_seed": 5,
}


# -- the adjacency from edge arrays ---------------------------------------------


def messy_arcs(n=60, arcs=500):
    """Duplicates, both directions of an edge, self-loops."""
    rng = np.random.default_rng(3)
    u, v = rng.integers(0, n, arcs), rng.integers(0, n, arcs)
    u = np.concatenate([u, v[:120], u[:60], np.arange(20)])
    v = np.concatenate([v, u[:120], v[:60], np.arange(20)])
    return u, v, n


def test_adjacency_from_edges_is_simple_graphs_on_a_messy_arc_list():
    u, v, n = messy_arcs()
    A = adjacency_from_edges(u, v, n)
    G = SimpleGraph(zip(u.tolist(), v.tolist()))
    assert A.shape == (n, n) and A.nse == G.volume
    assert A.dtype == jnp.float32 and A.indices.dtype == jnp.int32
    assert A.indices_sorted and A.unique_indices
    idx = np.asarray(A.indices)
    assert (np.lexsort((idx[:, 1], idx[:, 0])) == np.arange(len(idx))).all()
    dense, want = np.asarray(A.todense()), G.adjacency()
    ids = np.array([G.index.get(i, -1) for i in range(n)])  # SimpleGraph renumbers
    seen = ids >= 0
    np.testing.assert_array_equal(dense[np.ix_(seen, seen)],
                                  want[np.ix_(ids[seen], ids[seen])])
    assert dense[~seen].sum() == 0 and dense.diagonal().sum() == 0
    np.testing.assert_array_equal(dense, dense.T)
    assert set(np.unique(dense)) == {0.0, 1.0}


def test_adjacency_from_edges_drops_arcs_that_leave_the_vertex_set():
    A = adjacency_from_edges([0, 1, 5, -1, 2], [1, 2, 1, 0, 9], 4)
    np.testing.assert_array_equal(np.asarray(A.indices), [[0, 1], [1, 0], [1, 2], [2, 1]])
    assert adjacency_from_edges([2, 2], [2, 2], 3).nse == 0


# -- the sparse route against the plain reference and the dense route -----------


@pytest.fixture(scope="module")
def planted():
    with jax.enable_x64(False):
        u, v = ENTRY.make_arcs(PLANTED, 11)
        return np.asarray(u), np.asarray(v), PLANTED["vertices"]


def prepared(A, hot_rows=None):
    """``prepare(A, symmetric=True)``; with ``hot_rows``, as a graph of
    more than ``HOT_ROWS`` vertices is prepared (the constant is read
    where the layout is made; the product reads the layout)."""
    with pytest.MonkeyPatch.context() as patch:
        if hot_rows:
            patch.setattr(sparse, "HOT_ROWS", hot_rows)
        A = prepare(A, symmetric=True)
    assert A.hot.shape == ((hot_rows,) if hot_rows else (0,))
    return A


@pytest.mark.parametrize("hot_rows", [None, 1024])
@pytest.mark.parametrize("q", [0, 2])
def test_the_sparse_route_agrees_with_the_entrys_plain_reference(planted, q, hot_rows):
    """The benchmark's own comparison at a small size: the same recurrence
    from the same Omega, the product and the orthonormalization each done
    another way.  Eight leading eigenvalues stand clear of the ninth, so
    f32 rounding (1e-7 a sum, amplified by |lambda_1| / gap, under 100) is
    all that separates the two: 2e-5 leaves ten times of room.  With a
    hot table a row's terms are summed in another grouping, no more."""
    u, v, n = planted
    k, s = 5, 10
    with jax.enable_x64(False):
        A = prepared(adjacency_from_edges(u, v, n), hot_rows)
        (X, lam), info = approximate_ase(
            A, k, SketchContext(seed=17), ASEParams(num_iterations=q, sparse=True),
            return_info=True)
        omega = JLT(n, s, SketchContext(seed=17)).realize(jnp.float32)
        ref, ritz_values = ENTRY.reference_ase(
            jnp.asarray(u), jnp.asarray(v), n, omega, k, q, 8192)
        errs = [float(e) for e in ENTRY.compare(ENTRY.pack(lam, X), ref, jnp.arange(0, n, 7))]
    assert info["products"] == 2 + 2 * q and info["nnz"] == A.nse
    if hot_rows:  # a third of the 3,000 vertices holds six tenths of a rank law's nonzeros
        assert info["tables"] == A.tables == 2 and len(info) == 6
        assert info["hot_share"] == A.hot_share == A.hot_nse / A.nse and 0.5 < A.hot_share < 0.8
    else:         # the four keys it had; the operand says the rest
        assert set(info) == {"products", "iterations", "nnz", "edge_chunks"}
        assert (A.tables, A.hot_share) == (1, 0.0)
    assert abs(ritz_values[k - 1]) > 1.5 * abs(ritz_values[k]) or q == 0
    assert max(errs) < 2e-5, errs


def test_sparse_prepared_and_dense_routes_agree(planted):
    u, v, n = planted
    A = adjacency_from_edges(u, v, n)
    params = ASEParams(num_iterations=2, sparse=True)
    (Xd, ld), (Xb, lb), (Xp, lp) = (
        approximate_ase(op, 5, SketchContext(seed=2), params)
        for op in (A.todense(), A, prepare(A, symmetric=True)))
    for X, lam in ((Xb, lb), (Xp, lp)):
        np.testing.assert_allclose(lam, ld, rtol=1e-5)
        np.testing.assert_allclose(np.abs(X), np.abs(Xd), rtol=1e-3, atol=1e-4)


def test_a_simple_graph_goes_through_the_prepared_operand(planted, monkeypatch):
    u, v, n = planted
    G = SimpleGraph(zip(u[:4000].tolist(), v[:4000].tolist()))
    seen = []
    real = svd.approximate_symmetric_svd
    monkeypatch.setattr(
        "libskylark_tpu.graph.ase.approximate_symmetric_svd",
        lambda A, *a, **kw: seen.append(A) or real(A, *a, **kw))
    Xs, ls = approximate_ase(G, 3, SketchContext(seed=2), ASEParams(num_iterations=2, sparse=True))
    Xd, ld = approximate_ase(G, 3, SketchContext(seed=2), ASEParams(num_iterations=2))
    assert isinstance(seen[0], Prepared) and seen[0].symmetric
    np.testing.assert_allclose(ls, ld, rtol=1e-5)
    np.testing.assert_allclose(np.abs(Xs), np.abs(Xd), rtol=1e-3, atol=1e-4)


# -- the dense route, to the bit ------------------------------------------------


def eager_symmetric_svd(A, rank, context, params):
    """``approximate_symmetric_svd`` as it stood before PR 37: op by op,
    the sweeps a ``fori_loop`` traced anew every call."""
    n = A.shape[0]
    k, s = svd._sketch_size(rank, params, n)
    Y = JLT(n, s, context).apply(A, "rowwise")
    Y = svd.power_iteration(A, Y, params.num_iterations, not params.skip_qr)
    Q = Y if (params.num_iterations > 0 and not params.skip_qr) else svd.gram_orth(Y)
    AQ = jnp.dot(A, Q, precision="highest")
    T = jnp.dot(Q.T, AQ, precision="highest")
    T = (T + T.T) / 2
    lam, W = jnp.linalg.eigh(T)
    order = jnp.argsort(-jnp.abs(lam))
    return jnp.dot(Q, W, precision="highest")[:, order[:k]], lam[order][:k]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
@pytest.mark.parametrize("n,k,q", [(60, 2, 3), (257, 8, 2), (513, 5, 1), (1000, 8, 2)])
def test_the_dense_route_is_the_eager_recurrence_to_the_bit(n, k, q, dtype):
    rng = np.random.default_rng(n)
    A = np.triu((rng.random((n, n)) < 0.1), 1)
    A = jnp.asarray(A + A.T, dtype)
    params = SVDParams(num_iterations=q)
    V0, l0 = eager_symmetric_svd(A, k, SketchContext(seed=3), params)
    V1, l1 = approximate_symmetric_svd(A, k, SketchContext(seed=3), params)
    X1, l2 = approximate_ase(A, k, SketchContext(seed=3), ASEParams(num_iterations=q))
    assert np.asarray(V0).tobytes() == np.asarray(V1).tobytes()
    assert np.asarray(l0).tobytes() == np.asarray(l1).tobytes() == np.asarray(l2).tobytes()
    X0 = V0 * jnp.sqrt(jnp.abs(l0))[None, :]
    assert np.asarray(X0).tobytes() == np.asarray(X1).tobytes()


@pytest.mark.parametrize("params", [SVDParams(num_iterations=0),
                                    SVDParams(num_iterations=2, skip_qr=True)],
                         ids=["no_sweep", "skip_qr"])
def test_where_the_ritz_program_orthonormalizes_the_last_bits_may_differ(params):
    """The Gram passes then run inside ``_ritz``, their transposes folded
    into the products: the same numbers to rounding, up to a vector's sign."""
    rng = np.random.default_rng(1)
    A = np.triu((rng.random((300, 300)) < 0.1), 1)
    A = jnp.asarray(A + A.T, jnp.float32)
    V0, l0 = eager_symmetric_svd(A, 4, SketchContext(seed=3), params)
    V1, l1 = approximate_symmetric_svd(A, 4, SketchContext(seed=3), params)
    np.testing.assert_allclose(l1, l0, rtol=2e-5)
    np.testing.assert_allclose(np.abs(V1), np.abs(V0), atol=2e-4)


# -- a warm call builds nothing -------------------------------------------------


@pytest.mark.parametrize("form", ["dense", "prepared", "hot_table"])
def test_a_warm_call_traces_and_lowers_nothing(planted, form):
    u, v, n = planted
    if form == "hot_table":  # (every arc: a hot table pays from a mean degree of 30 or so)
        A = prepared(adjacency_from_edges(u, v, n), 1024)
    else:
        A = adjacency_from_edges(u[:20000], v[:20000], n)
        A = prepared(A) if form == "prepared" else A.todense()

    def call(q):
        (X, lam), info = approximate_ase(
            A, 4, SketchContext(seed=5), ASEParams(num_iterations=q, sparse=True),
            return_info=True)
        return jax.block_until_ready(X)

    call(2)
    with builds() as seen:
        call(2)
        call(3)  # the budget is a scalar: another count of sweeps, the same programs
    assert seen == []


def test_the_programs_have_the_names_the_benchmark_reads():
    import json
    import re

    with open(os.path.join(REPO, "benchmarks", "layer_metrics",
                           "ase_product_roofline.json")) as f:
        pattern = json.load(f)["reader"]["module"]
    for fn in (svd._sym_sketch, svd._chunk, svd._ritz):
        assert re.search(pattern, "jit_" + fn.__name__), fn.__name__
    assert not re.search(pattern, "jit__project") and not re.search(pattern, "jit__embed")


# -- the CLI ----------------------------------------------------------------------


def test_graph_se_cli_builds_an_integer_arc_list_on_the_device(tmp_path, monkeypatch, capsys):
    from libskylark_tpu.cli.graph_se import main

    # two planted communities: two eigenvalues far above the rest, so the two
    # routes (another vertex order, so another start) find the same two vectors
    rng = np.random.default_rng(4)
    i, j = np.triu_indices(80, 1)
    keep = rng.random(len(i)) < np.where((i < 40) == (j < 40), 0.6, 0.05)
    names = rng.permutation(80)
    u, v = names[i[keep]], names[j[keep]]
    u, v = np.concatenate([u, v[:50], [7, 7]]), np.concatenate([v, u[:50], [7, 9]])
    (tmp_path / "g").write_text(
        "# arcs\n" + "\n".join(f"{a + 100} {b + 100}" for a, b in zip(u, v)) + "\n")
    monkeypatch.chdir(tmp_path)
    assert main([str(tmp_path / "g"), "-k", "2", "--sparse", "--prefix", "s"]) == 0
    out = capsys.readouterr().out
    assert "6 products" in out and "on the device" in out
    assert main([str(tmp_path / "g"), "-k", "2", "--prefix", "d"]) == 0
    Xs, Xd = np.load(tmp_path / "s.X.npy"), np.load(tmp_path / "d.X.npy")
    names_s = (tmp_path / "s.index.txt").read_text().split()
    names_d = (tmp_path / "d.index.txt").read_text().split()
    assert names_s == sorted(names_s, key=int) and sorted(names_s) == sorted(names_d)
    at = [names_d.index(name) for name in names_s]  # the device route sorts the ids
    np.testing.assert_allclose(np.abs(Xs), np.abs(Xd[at]), atol=0.05)


def test_graph_se_cli_keeps_named_vertices_on_the_host_route(tmp_path, monkeypatch, capsys):
    from libskylark_tpu.cli.graph_se import main

    (tmp_path / "g").write_text("a b\nb c\nc a\nc d\nd e\ne c\n")
    monkeypatch.chdir(tmp_path)
    assert main([str(tmp_path / "g"), "-k", "2", "--sparse", "--prefix", "n"]) == 0
    assert "on the device" not in capsys.readouterr().out
    assert (tmp_path / "n.index.txt").read_text().split() == list("abcde")
