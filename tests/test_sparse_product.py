"""The sparse-times-panel product (``core.sparse``): against a dense A and
against ``bcoo_dot_general``, for A·Y and Aᵀ·Y, a panel and a vector, an
operand prepared from a BCOO in any order, chunk and table sizes that do
and do not divide what they cut, rows and columns with no nonzero, rows
far heavier than the rest; and what the layout of a prepared operand holds.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import sparse as jsparse

from libskylark_tpu.core import sparse as sp
from libskylark_tpu.sketch import JLT
from libskylark_tpu import SketchContext

F32 = np.float32


def operand(seed, m, n, nnz, heavy=0, order="shuffled"):
    """(BCOO, dense) of an m x n f32 matrix with about ``nnz`` distinct
    nonzeros, ``heavy`` of them in row 3; a tenth of the rows and of the
    columns hold none."""
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, m - m // 10, nnz)
    cols = rng.integers(n // 10, n, nnz)
    rows[:heavy] = 3
    keys = np.unique(rows * n + cols)
    rows, cols = keys // n, keys % n
    vals = rng.normal(size=len(keys)).astype(F32)
    dense = np.zeros((m, n), F32)
    dense[rows, cols] = vals
    at = rng.permutation(len(keys)) if order == "shuffled" else np.arange(len(keys))
    idx = np.stack([rows[at], cols[at]], axis=1).astype(np.int32)
    A = jsparse.BCOO((jnp.asarray(vals[at]), jnp.asarray(idx)), shape=(m, n),
                     indices_sorted=order == "sorted", unique_indices=True)
    return A, dense


@pytest.fixture
def sizes(monkeypatch):
    """Set the module's chunk and table sizes for one test."""
    def set_(chunk_bytes, table_rows):
        monkeypatch.setattr(sp, "CHUNK_BYTES", chunk_bytes)
        monkeypatch.setattr(sp, "TABLE_ROWS", table_rows)
        sp.spmm.clear_cache()  # the sizes are read when a product is traced
    yield set_
    sp.spmm.clear_cache()


SHAPES = {  # m, n, nnz, heavy, chunk bytes (of 5 f32 columns), table rows
    "one_chunk_one_table": (50, 40, 300, 0, 1 << 26, 3 << 19),
    "chunks_divide": (96, 64, 1024, 0, 128 * 5 * 4, 16),
    "chunks_do_not_divide": (300, 300, 5000, 1200, 1 << 12, 64),
    "tall_ragged_tables": (1000, 700, 20000, 3000, 1 << 14, 128),
}


@pytest.mark.parametrize("seed", [1, 21])
@pytest.mark.parametrize("order", ["sorted", "shuffled"])
@pytest.mark.parametrize("shape", SHAPES)
def test_product_and_transposed_product_match_the_dense_matrix(shape, order, seed, sizes):
    m, n, nnz, heavy, chunk_bytes, table = SHAPES[shape]
    sizes(chunk_bytes, table)
    A, dense = operand(seed, m, n, nnz, heavy, order)
    rng = np.random.default_rng(seed + 1)
    Y, Z = rng.normal(size=(n, 5)).astype(F32), rng.normal(size=(m, 5)).astype(F32)
    # Aᵀ's layout is the layout of the transposed BCOO (unsorted, then)
    ops = sp.prepare(A), sp.prepare(A.T)
    got = sp.spmm(ops[0], Y), sp.spmm(ops[1], Z)
    assert ops[0].shape == (m, n) and ops[1].shape == (n, m)
    assert ops[0].nse == ops[1].nse == A.nse
    # f32 sums in another order than the dense product's: a few ulps of the
    # sum of the terms' sizes (the heavy row has thousands)
    for out, M, X in ((got[0], dense, Y), (got[1], dense.T, Z)):
        assert out.dtype == jnp.float32 and out.shape == (M.shape[0], 5)
        bound = 4e-7 * (np.abs(M).astype(np.float64) @ np.abs(X)).max() + 1e-7
        assert np.abs(np.asarray(out) - M.astype(np.float64) @ X).max() <= 16 * bound
    np.testing.assert_array_equal(np.asarray(got[0])[m - m // 10:], 0)  # empty rows


@pytest.mark.parametrize("table", [64, 150])
def test_product_matches_bcoo_dot_general_and_takes_a_vector(table, sizes):
    sizes(1 << 12, table)
    A, dense = operand(3, 200, 150, 3000, 400)
    op = sp.prepare(A)
    Y = np.random.default_rng(4).normal(size=(150, 7)).astype(F32)
    np.testing.assert_allclose(sp.spmm(op, Y), A @ jnp.asarray(Y), rtol=2e-5, atol=2e-5)
    y = sp.spmm(op, Y[:, 0])
    assert y.shape == (200,)
    np.testing.assert_array_equal(y, sp.spmm(op, Y[:, :1])[:, 0])
    # under a jit the operand is an argument like any other
    np.testing.assert_allclose(jax.jit(sp.spmm)(op, Y), sp.spmm(op, Y), rtol=2e-5, atol=2e-5)


def test_a_symmetric_operand_serves_both_products_and_another_refuses(sizes):
    sizes(1 << 12, 32)
    A, dense = operand(5, 120, 120, 1500)
    S = jsparse.BCOO.fromdense(jnp.asarray(dense + dense.T))
    Y = np.random.default_rng(6).normal(size=(120, 4)).astype(F32)
    op = sp.prepare(S, symmetric=True)
    np.testing.assert_array_equal(sp.spmm(op, Y, transpose=True), sp.spmm(op, Y))
    np.testing.assert_allclose(sp.spmm(op, Y), (dense + dense.T) @ Y, rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="multiplies as A·Y alone"):
        sp.spmm(sp.prepare(A), Y, transpose=True)
    with pytest.raises(ValueError, match="Y is"):
        sp.spmm(op, Y[:-1])
    with pytest.raises(TypeError, match="prepared operand"):
        sp.spmm(S, Y)  # a plain BCOO goes through A @ Y


def test_an_operand_with_no_nonzero_gives_zeros(sizes):
    sizes(1 << 10, 16)
    A = jsparse.BCOO((jnp.zeros((0,), jnp.float32), jnp.zeros((0, 2), jnp.int32)),
                     shape=(64, 48))
    Y = jnp.ones((48, 3), jnp.float32)
    np.testing.assert_array_equal(sp.spmm(sp.prepare(A), Y), np.zeros((64, 3), F32))


def test_padding_indices_of_a_bcoo_read_zero_and_add_nowhere(sizes):
    """``BCOO.fromdense(..., nse=more)`` pads with indices at the shape."""
    sizes(1 << 10, 16)
    _, dense = operand(7, 40, 30, 200)
    A = jsparse.BCOO.fromdense(jnp.asarray(dense), nse=int((dense != 0).sum()) + 37)
    Y = np.random.default_rng(8).normal(size=(30, 3)).astype(F32)
    np.testing.assert_allclose(sp.spmm(sp.prepare(A), Y), dense @ Y, rtol=2e-5, atol=2e-5)


def test_the_layout_holds_every_nonzero_once_in_buckets_of_equal_count(sizes):
    sizes(1 << 12, 64)
    A, dense = operand(9, 300, 200, 6000, 1500)
    op = sp.prepare(A)
    assert len(op.cols) == len(op.buckets) == -(-200 // 64)
    table = sp._table(200)                                  # four equal blocks
    assert table == 50
    live = 0
    for j, (cols, vals, place, buckets) in enumerate(
            zip(op.cols, op.vals, op.place, op.buckets)):
        cols, vals = np.asarray(cols), np.asarray(vals)
        assert cols.shape == vals.shape == (sp.PIECE, sum(r * k for r, k in buckets))
        assert sum(r for r, _ in buckets) == 300            # every row in one bucket
        assert [k for _, k in buckets] == sorted({k for _, k in buckets})
        assert set(k for _, k in buckets) <= set(sp._counts(1 << 20))
        assert sorted(np.asarray(place)) == list(range(300))
        pad = cols == table
        assert (vals[pad] == 0).all() and (cols[~pad] < table).all()
        live += int((~pad).sum())
        # a block's values are its columns' of the dense matrix
        assert np.isclose(vals.sum(), dense[:, j * table:(j + 1) * table].sum(), atol=1e-3)
    assert live == A.nse
    slots = sum(c.size for c in op.cols)
    assert slots <= 1.25 * A.nse + sp.PIECE * 300 * len(op.cols)


def test_piece_counts_are_four_to_the_octave():
    assert sp._counts(1) == list(range(1, 9))
    assert sp._counts(9) == [1, 2, 3, 4, 5, 6, 7, 8, 10, 12, 14, 16]
    got = sp._counts(5000)
    assert got[-1] >= 5000 and all(b / a <= 2 for a, b in zip(got, got[1:]))
    assert all(b / a <= 1.25 for a, b in zip(got[3:], got[4:]))


def test_chunks_are_counted_from_the_panel_width(sizes):
    sizes(1 << 12, 64)
    A, _ = operand(10, 300, 200, 6000)
    op = sp.prepare(A)
    slots = [c.size for c in op.cols]
    assert sp.edge_chunks(op, 4) == sum(-(-s // 256) for s in slots)   # 4096 / (4 * 4) slots a step
    assert sp.edge_chunks(op, 16) == sum(-(-s // 64) for s in slots)


@pytest.mark.parametrize("n, table, blocks, rows", [
    (1, 64, 1, 1), (64, 64, 1, 64), (65, 64, 2, 33), (200, 64, 4, 50),
    (1_843_465, 3 << 19, 2, 921_733), (3_072_441, 3 << 19, 2, 1_536_221)])
def test_the_columns_are_cut_into_the_fewest_equal_tables(n, table, blocks, rows, sizes):
    sizes(1 << 12, table)
    assert sp._table(n) == rows <= table
    assert -(-n // rows) == blocks == -(-n // table)


@pytest.mark.parametrize("rows", [150, 40000])
def test_the_symmetric_sketch_of_a_bcoo_is_the_product(rows, sizes):
    """``A·Ωᵀ`` as ``approximate_symmetric_svd`` takes it (``_sym_sketch``)
    is ``JLT.apply(A, ROWWISE)``'s product; of a prepared operand it holds
    a chunk of gathered rows where ``bcoo_dot_general`` (a plain BCOO's
    product there, and ``sketch/dense.py::_matmul``'s BCOO branch, both
    left as they were) holds one for every nonzero."""
    from libskylark_tpu.linalg import svd

    sizes(1 << 12, 64)
    A, dense = operand(11, rows, 90, 2000)
    S = JLT(90, 12, SketchContext(seed=5))
    Wt = S.realize(jnp.float32).T
    want = dense @ np.asarray(Wt)
    np.testing.assert_allclose(S.apply(A, "rowwise"), want, rtol=2e-5, atol=2e-5)
    op = sp.prepare(A)
    np.testing.assert_allclose(svd._sym_sketch(op, Wt), want, rtol=2e-5, atol=2e-5)
    assert f"tensor<{A.nse}x12xf32>" in svd._sym_sketch.lower(A, Wt).as_text()
    text = svd._sym_sketch.lower(op, Wt).as_text()
    for slots in {A.nse, *(c.size for c in op.cols)}:
        assert f"tensor<{slots}x12xf32>" not in text
    svd._sym_sketch.clear_cache()  # traced under this test's sizes
