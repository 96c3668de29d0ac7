"""Polynomial-kernel ridge regression on a sparse X: TensorSketch
(``sketch/ppt.py``) on a BCOO, never densified, and the streamed trainer
on the row panels of ``core.sparse.panels``.

A BCOO's features are its dense form's on both routes (the complex FFT,
exactly as computed; the bf16 half-spectrum DFT that the chip takes and
``SKYLARK_PPT_DFT=1`` forces here, to bf16 feature accuracy), rowwise and
columnwise, plain triplets or rows in slots (``core.sparse.RowSlots``:
K slots a row in place, the rest of a longer row in overflow rows), with
the CountSketches under and over the one-hot limit (the dense route
folds them into its tables under it; a sparse X never does).  Zero-valued
padding adds nothing.  Panels hold every row, and their slots grow with
the nonzeros, not with the longest row.  The trainer on panels is the
trainer on the dense rows, builds its three programs once a process, and
no (rows, d) array exists in its programs; the command line trains a
sparse X on panels.  Each level's buckets and signs are read at the
nonzeros' columns from their counters: ``buckets_at`` and ``values_at``
are ``buckets()`` and ``values()`` there.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _builds import builds
from jax.experimental import sparse as jsparse

from libskylark_tpu import SketchContext
from libskylark_tpu.core import sparse
from libskylark_tpu.core.random import sample, sample_at
from libskylark_tpu.ml import KrrParams, PolynomialKernel, krr, streaming_kernel_ridge
from libskylark_tpu.sketch import CWT, MMT, PPT, SJLT, WZT, hash as hash_mod

N, D, S, T, PANEL = 8192, 61, 64, 2, 4096  # a panel the DFT gate admits
PROGRAMS = (krr.gram, krr.zr, krr.apply_delta)


def sparse_rows(n, d, density, seed, dtype=jnp.float32):
    """A dense (n, d) array with about ``density`` of its entries nonzero,
    every row holding at least one."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)) * (rng.random((n, d)) < density)
    X[np.arange(n), rng.integers(0, d, n)] = 1.0
    return jnp.asarray(X, dtype)


@pytest.fixture(params=["fft", "dft"])
def route(request, monkeypatch):
    monkeypatch.delenv("SKYLARK_NO_PPT_DFT", raising=False)
    if request.param == "dft":
        monkeypatch.setenv("SKYLARK_PPT_DFT", "1")
    else:
        monkeypatch.delenv("SKYLARK_PPT_DFT", raising=False)
    return request.param


def close(got, want, route):
    """Equal as computed on the FFT route (f32), to bf16 feature accuracy
    on the DFT route."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert got.shape == want.shape
    if route == "fft":
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6 * np.abs(want).max())
    else:
        assert np.abs(got - want).max() <= 2e-2 * np.abs(want).max()


# -- the counters at scattered coordinates ------------------------------------


def test_sample_at_is_the_streams_sample_at_those_counters():
    base = (1 << 33) + 5  # a counter window that crosses 2^32 in its high word
    idx = jnp.asarray([[3, 999], [0, 500], [2**20 + 7, 1]], jnp.int32)
    for dist, kw in (("uniform_int", {"low": 0, "high": 4095}), ("rademacher", {}),
                     ("cauchy", {}), ("normal", {})):
        dtype = jnp.int32 if dist == "uniform_int" else jnp.float32
        whole = sample(dist, 11, base, 2**20 + 8, dtype=dtype, **kw)
        np.testing.assert_array_equal(
            np.asarray(sample_at(dist, 11, base, idx, dtype=dtype, **kw)),
            np.asarray(whole)[np.asarray(idx)])


@pytest.mark.parametrize("cls,kw", [(CWT, {}), (SJLT, {"nnz": 3}), (MMT, {}), (WZT, {})],
                         ids=["CWT", "SJLT", "MMT", "WZT"])
def test_buckets_and_values_at_are_the_arrays_read_there(cls, kw):
    H = cls(50, 16, SketchContext(seed=3), **kw)
    idx = jnp.asarray([[1, 49], [0, 7], [H.nnz * 50 - 1, 13]], jnp.int32)
    np.testing.assert_array_equal(np.asarray(H.buckets_at(idx)), np.asarray(H.buckets())[idx])
    np.testing.assert_array_equal(np.asarray(H.values_at(idx)), np.asarray(H.values())[idx])


def long_tailed_rows(n, d, seed, dtype=jnp.float32, longest=None):
    """A dense (n, d) array whose rows hold from 1 to ``longest`` nonzeros
    (about 5 most of them, a few many more), so that row slots need
    overflow rows."""
    rng = np.random.default_rng(seed)
    counts = np.minimum(rng.geometric(0.2, n), d)
    counts[rng.integers(0, n, max(n // 16, 1))] = longest or d // 2
    X = np.zeros((n, d))
    for r, c in enumerate(counts):
        X[r, rng.choice(d, c, replace=False)] = rng.standard_normal(c)
    return jnp.asarray(X, dtype)


def dense_of(rs):
    """The dense form of ``core.sparse.RowSlots``, padding included."""
    m, n = rs.shape
    out = np.zeros((m, n), np.float64)
    data, cols = np.asarray(rs.data, np.float64), np.asarray(rs.cols)
    np.add.at(out, (np.repeat(np.arange(m), cols.shape[1]), cols.ravel()), data.ravel())
    xd, xc = np.asarray(rs.xdata, np.float64), np.asarray(rs.xcols)
    np.add.at(out, (np.repeat(np.asarray(rs.xrows), xc.shape[1]), xc.ravel()), xd.ravel())
    return out


@pytest.mark.parametrize("cls,kw", [(CWT, {}), (SJLT, {"nnz": 3}), (WZT, {})],
                         ids=["CWT", "SJLT", "WZT"])
def test_row_slots_hash_as_the_dense_rows_do(cls, kw):
    H = cls(61, 32, SketchContext(seed=4), **kw)
    X = long_tailed_rows(40, 61, 1)
    A = jsparse.BCOO.fromdense(X)
    rs = sparse.row_slots(A)
    assert rs.xdata.shape[0] > 0  # the long rows go on in overflow rows
    want = np.asarray(H.apply(X, "rowwise"))
    for got in (H.apply(rs, "rowwise"), H.apply(A, "rowwise", dense_output=True),
                jax.jit(lambda A: H.apply(A, "rowwise", dense_output=True))(A),
                jax.jit(lambda rs: H.apply(rs, "rowwise"))(rs)):
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(H.apply(A.T, "columnwise", dense_output=True)),
                               np.asarray(H.apply(X.T, "columnwise")), rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="rowwise"):
        H.apply(rs, "columnwise")


# -- the map on a BCOO ---------------------------------------------------------


@pytest.mark.parametrize("folds", [True, False], ids=["under_limit", "over_limit"])
@pytest.mark.parametrize("dim,layout", [("rowwise", "triplets"), ("rowwise", "row_slots"),
                                        ("columnwise", "triplets")])
def test_ppt_on_a_bcoo_is_ppt_on_its_dense_form(route, dim, layout, folds, monkeypatch):
    if not folds:  # the CountSketches over the one-hot limit: the dense route unfolded
        monkeypatch.setattr(hash_mod.HashSketch, "_ONEHOT_LIMIT", 1)
    dtype = jnp.bfloat16 if route == "dft" else jnp.float32
    M = PPT(D, S, SketchContext(seed=5), q=2, c=1.0, gamma=0.2)
    assert M._folds() == folds
    X = long_tailed_rows(PANEL, D, 2, dtype, longest=40)
    X = X if dim == "rowwise" else X.T
    A = jsparse.BCOO.fromdense(X)
    if layout == "row_slots":
        A = sparse.row_slots(A)
    assert M._dft_wins(jnp.dtype(dtype), PANEL) == (route == "dft")
    want = M.apply(X, dim)
    got = M.apply(A, dim)
    assert got.dtype == want.dtype
    close(got, want, route)
    # a sparse input's hoisted operands (the plain tables) serve it too
    ops = M.hoistable_operands(dtype, sparse=True)
    np.testing.assert_array_equal(np.asarray(M.apply_with_operands(ops, A, dim)),
                                  np.asarray(got))
    traced = jax.jit(lambda A: M.apply_with_operands(
        M.hoistable_operands(dtype, sparse=True), A, dim))
    close(traced(A), want, route)


def test_operands_hoisted_for_the_other_input_are_refused(monkeypatch):
    """On the DFT route under the one-hot limit a dense input takes folded
    tables and a sparse one the plain tables: each kind's operands are
    refused for the other."""
    monkeypatch.setenv("SKYLARK_PPT_DFT", "1")
    M = PPT(D, S, SketchContext(seed=6), q=2, c=1.0, gamma=0.2)
    X = long_tailed_rows(PANEL, D, 3, jnp.bfloat16, longest=40)
    A = jsparse.BCOO.fromdense(X)
    assert M._folds() and M._dft_wins(jnp.dtype(jnp.bfloat16), PANEL)
    with pytest.raises(ValueError, match="hoisted for a dense input"):
        M.apply_with_operands(M.hoistable_operands(jnp.bfloat16), A, "rowwise")
    with pytest.raises(ValueError, match="hoisted for a sparse input"):
        M.apply_with_operands(M.hoistable_operands(jnp.bfloat16, sparse=True), X, "rowwise")


def test_zero_valued_padding_changes_no_feature(route):
    dtype = jnp.bfloat16 if route == "dft" else jnp.float32
    M = PPT(D, S, SketchContext(seed=7), q=2, c=1.0, gamma=0.2)
    X = long_tailed_rows(PANEL, D, 3, dtype, longest=40)
    rs = sparse.row_slots(jsparse.BCOO.fromdense(X))
    assert rs.xdata.shape[0] > 0
    rng = np.random.default_rng(4)
    # eight more slots a line and eight more overflow rows, value 0, at
    # scattered columns and rows
    pad = lambda a, k: jnp.concatenate([a, jnp.zeros((a.shape[0], k), a.dtype)], 1)
    cols = lambda r: jnp.asarray(rng.integers(0, D, (r, 8)), jnp.int32)
    e, k = rs.xdata.shape[0], rs.data.shape[1] + 8
    wide = sparse.RowSlots(
        pad(rs.data, 8), jnp.concatenate([rs.cols, cols(PANEL)], 1),
        jnp.concatenate([pad(rs.xdata, 8), jnp.zeros((8, k), dtype)]),
        jnp.concatenate([jnp.concatenate([rs.xcols, cols(e)], 1),
                         jnp.asarray(rng.integers(0, D, (8, k)), jnp.int32)]),
        jnp.concatenate([rs.xrows, jnp.full((8,), PANEL - 1, jnp.int32)]), rs.shape)
    np.testing.assert_array_equal(dense_of(wide), dense_of(rs))
    close(M.apply(wide, "rowwise"), M.apply(rs, "rowwise"), route)
    np.testing.assert_array_equal(np.asarray(M.apply(wide, "rowwise")),
                                  np.asarray(M.apply(rs, "rowwise")))


def test_a_bcoo_of_other_shape_is_refused():
    M = PPT(D, S, SketchContext(seed=8), q=2)
    A = jsparse.BCOO.fromdense(sparse_rows(16, D + 1, 0.2, 5))
    with pytest.raises(ValueError, match="cols"):
        M.apply(A, "rowwise")
    with pytest.raises(ValueError, match="rows"):
        M.apply(A, "columnwise")
    with pytest.raises(ValueError, match="rowwise"):
        M.apply(sparse.row_slots(A), "columnwise")


# -- the row panels -------------------------------------------------------------


@pytest.mark.parametrize("form", ["sorted", "shuffled", "host"])
def test_panels_hold_every_row_in_their_slots(form):
    X = np.asarray(long_tailed_rows(96, 200, 6, longest=150))
    A = jsparse.BCOO.fromdense(jnp.asarray(X))
    if form == "shuffled":
        perm = np.random.default_rng(7).permutation(A.nse)
        A = jsparse.BCOO((A.data[perm], A.indices[perm]), shape=A.shape)
        P = sparse.panels(A, 32)
    elif form == "host":
        P = sparse.panels((np.asarray(A.data), np.asarray(A.indices)), 32, shape=A.shape)
    else:
        P = sparse.panels(A, 32)
    k, e = P.data.shape[2], P.xdata.shape[1]
    assert (P.data.shape, P.xdata.shape, P.xrows.shape) == ((3, 32, k), (3, e, k), (3, e))
    assert e > 0 and k % 8 == 0
    assert P.nse == (X != 0).sum() and P.shape == (96, 200) and P.rows == 32
    for p in range(3):
        panel = sparse.panel_rows(p * 32, 32, P)
        assert isinstance(panel, sparse.RowSlots) and panel.shape == (32, 200)
        assert (np.diff(np.asarray(panel.xrows)) >= 0).all()
        np.testing.assert_array_equal(dense_of(panel), X[32 * p:32 * (p + 1)])
    np.testing.assert_array_equal(dense_of(jax.jit(lambda P, p: P.panel(p))(P, 2)), X[64:])


def test_the_slots_grow_with_the_nonzeros_not_the_longest_row():
    """Rows of 5 nonzeros and one of 1,000 a panel: K stays small and the
    long rows go on in overflow rows, so the slots are a few times the
    nonzeros and not rows x the longest row."""
    rows, n = 512, 4000
    count = np.full(4 * rows, 5)
    count[::rows] = 1000
    k, e = sparse._slot_width(count, rows)
    assert k <= 16 and e >= -(-1000 // k) - 1
    assert 4 * (rows + e) * k < 3 * count.sum() < 4 * rows * 1000
    # the widest K where every row fits it and lines cost their bins
    assert sparse._slot_width(np.full(2 * rows, 100), rows) == (104, 0)


def test_panels_refuse_what_they_cannot_cut():
    A = jsparse.BCOO.fromdense(sparse_rows(96, 37, 0.15, 6))
    with pytest.raises(ValueError, match="panels of 40"):
        sparse.panels(A, 40)
    with pytest.raises(ValueError, match="plain 2-D BCOO"):
        sparse.panels(jsparse.BCOO.fromdense(A.todense(), n_batch=1), 32)
    with pytest.raises(ValueError, match="asked for 16"):
        sparse.panel_rows(0, 16, sparse.panels(A, 32))
    with pytest.raises(ValueError, match="shape"):
        sparse.panels((np.asarray(A.data), np.asarray(A.indices)), 32)


# -- the trainer on panels ------------------------------------------------------


def rows_of(start, rows, X):
    return jax.lax.dynamic_slice_in_dim(X, start, rows, 0)


def train(block_fn, block_args, Y, dtype, seed=3):
    """One call as a user makes it: a new kernel, context and maps."""
    return streaming_kernel_ridge(
        PolynomialKernel(D, q=2, c=1.0, gamma=0.2), block_fn, (N, D), Y, 0.5, S,
        SketchContext(seed=seed), KrrParams(max_split=2 * S, iter_lim=2),
        block_rows=PANEL, feature_dtype=dtype, block_args=block_args)


@pytest.fixture
def empty_caches():
    for p in PROGRAMS:
        p.clear_cache()
    yield
    for p in PROGRAMS:
        p.clear_cache()


def test_the_trainer_on_panels_is_the_trainer_on_the_dense_rows(route, empty_caches):
    dtype = jnp.bfloat16 if route == "dft" else jnp.float32
    X = long_tailed_rows(N, D, 8, dtype, longest=40)
    Y = jnp.asarray(np.sign(np.random.default_rng(9).standard_normal((N, T))), jnp.float32)
    P = sparse.panels(jsparse.BCOO.fromdense(X), PANEL)
    assert P.xdata.shape[1] > 0
    dense = train(rows_of, (X,), Y, dtype)
    cold = train(sparse.panel_rows, (P,), Y, dtype)
    assert cold.info == {"feature_passes": 5, "feature_map": "PPT",
                         "feature_nnz": P.nse * 2 * 5}
    assert "feature_nnz" not in dense.info
    got, want = np.asarray(cold.W), np.asarray(dense.W)
    tol = 1e-4 if route == "fft" else 5e-2
    assert np.abs(got - want).max() <= tol * np.abs(want).max()
    # built once a process: a warm call with a new kernel, context and maps
    assert [p._cache_size() for p in PROGRAMS] == [2, 2, 2]
    with builds() as seen:
        warm = train(sparse.panel_rows, (P,), Y, dtype)
    assert seen == []
    assert [p._cache_size() for p in PROGRAMS] == [2, 2, 2]
    assert np.asarray(warm.W).tobytes() == got.tobytes()


@pytest.mark.parametrize("program", ["gram", "zr", "apply_delta"])
def test_no_rows_by_d_array_in_the_panel_programs(route, program):
    """The panel is never densified: no value of shape (rows, d) in the
    program, at a d no other size shares."""
    d, rows = 5003, PANEL
    dtype = jnp.bfloat16 if route == "dft" else jnp.float32
    X = sparse_rows(2 * rows, d, 0.002, 10, dtype)
    P = sparse.panels(jsparse.BCOO.fromdense(X), rows)
    M = PolynomialKernel(d, q=2, c=1.0, gamma=0.2).create_rft(S, "regular", SketchContext(seed=9))
    progs = dict(zip(("gram", "zr", "apply_delta"), krr.streaming_krr_chunk_programs(
        [M], 0, 2, rows, sparse.panel_rows, dtype)))
    lam, R, W = jnp.float32(1), jnp.zeros((2, rows, T), jnp.float32), jnp.zeros((S, T))
    args = {"gram": (lam, P), "zr": (lam, R, W, P), "apply_delta": (R, W, P)}[program]
    prog = progs[program]
    jaxpr = jax.make_jaxpr(lambda *a: prog.program(*prog.static, *a))(*args)

    def shapes(jx):
        for eqn in jx.eqns:
            for v in eqn.outvars:
                yield tuple(getattr(v.aval, "shape", ()))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)

    seen = set(shapes(jaxpr.jaxpr))
    assert (rows, S) in seen  # the bins are there
    assert not any(d in shape and rows in shape for shape in seen)


# -- the command line -------------------------------------------------------------


def test_skylark_krr_polynomial_sparse_predicts_as_the_dense_run(tmp_path, capsys):
    """``skylark_krr -k polynomial --sparse -a 2`` on a LIBSVM file trains
    on the row panels through the streamed trainer (it reports the
    nonzeros fed to the map: nonzeros x 2 levels x 5 panel passes), and
    the model predicts as the one trained from the dense rows, on the
    test file read either way."""
    from libskylark_tpu.cli.krr import main
    from libskylark_tpu.io import read_libsvm, write_libsvm
    from libskylark_tpu.ml import load_model

    rng = np.random.default_rng(11)
    X = np.asarray(long_tailed_rows(96, 40, 12, longest=30))
    y = np.where(X @ rng.standard_normal(40) > 0, 1, 2)
    write_libsvm(str(tmp_path / "train"), X[:80], y[:80])
    write_libsvm(str(tmp_path / "test"), X[80:], y[80:])
    preds = {}
    for mode in ("sparse", "dense"):
        model = str(tmp_path / f"{mode}.json")
        rc = main(["--trainfile", str(tmp_path / "train"), "--testfile", str(tmp_path / "test"),
                   "--modelfile", model, "-a", "2", "-k", "polynomial", "--gamma", "0.1",
                   "-f", "128", *(["--sparse"] if mode == "sparse" else [])])
        out = capsys.readouterr().out
        assert rc == 0 and "Test accuracy:" in out
        route = f"1 panels of 80 rows, {(X[:80] != 0).sum() * 2 * 5} nonzeros fed to the map"
        assert (route in out) == (mode == "sparse")
        Xt, _ = read_libsvm(str(tmp_path / "test"), sparse=mode == "sparse", n_features=40)
        preds[mode] = np.asarray(load_model(model).predict(Xt))
    np.testing.assert_allclose(preds["sparse"], preds["dense"], rtol=1e-4,
                               atol=1e-4 * np.abs(preds["dense"]).max())
