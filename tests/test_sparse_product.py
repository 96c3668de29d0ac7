"""The sparse-times-panel product (``core.sparse``): against a dense A and
against ``bcoo_dot_general``, for A·Y and Aᵀ·Y, a panel and a vector, an
operand prepared from a BCOO in any order, chunk and table sizes that do
and do not divide what they cut, rows and columns with no nonzero, rows
far heavier than the rest; what the layout of a prepared operand holds;
and the hot table: which operands get one, and that the others keep the
layout they had.
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


def skewed(seed, n, draws, symmetric, order="shuffled", exponent=1.0):
    """(BCOO, dense) of an n x n f32 matrix whose column counts follow a
    rank law under random labels (both ends for ``symmetric``, which
    gives A + Aᵀ; else the columns alone, the rows uniform)."""
    rng = np.random.default_rng(seed)
    w = (np.arange(n) + 1.0) ** -exponent
    label = rng.permutation(n)
    cols = label[rng.choice(n, draws, p=w / w.sum())]
    rows = label[rng.choice(n, draws, p=w / w.sum())] if symmetric else rng.integers(0, n, draws)
    dense = np.zeros((n, n), F32)
    dense[rows, cols] = rng.normal(size=draws).astype(F32)
    if symmetric:
        dense = dense + dense.T
    rows, cols = np.nonzero(dense)
    at = rng.permutation(len(rows)) if order == "shuffled" else np.arange(len(rows))
    idx = np.stack([rows[at], cols[at]], axis=1).astype(np.int32)
    A = jsparse.BCOO((jnp.asarray(dense[rows[at], cols[at]]), jnp.asarray(idx)),
                     shape=(n, n), indices_sorted=order == "sorted", unique_indices=True)
    return A, dense


@pytest.fixture
def sizes(monkeypatch):
    """Set the module's chunk and table sizes for one test (no hot table
    unless its rows are given: every operand here has fewer columns than
    the module's own ``HOT_ROWS``)."""
    def set_(chunk_bytes, table_rows, hot_rows=None):
        monkeypatch.setattr(sp, "CHUNK_BYTES", chunk_bytes)
        monkeypatch.setattr(sp, "TABLE_ROWS", table_rows)
        if hot_rows is not None:
            monkeypatch.setattr(sp, "HOT_ROWS", hot_rows)
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


def nonzeros_of(op):
    """(row, column, value) of every live slot of a prepared operand, read
    back through the layout: a table's pieces lie bucket after bucket,
    piece-major, ``place`` says where a row's sums come from, and a local
    column is a row of ``hot`` or of the table's slice of the columns."""
    m, n = op.shape
    table, hot = sp._table(n), np.asarray(op.hot)
    found = []
    for j, (cols, vals, place, buckets) in enumerate(
            zip(op.cols, op.vals, op.place, op.buckets)):
        cols, vals = np.asarray(cols), np.asarray(vals)
        is_hot = bool(hot.size) and j == 0
        rows_t = hot.size if is_hot else table
        bucketed = np.concatenate([np.tile(lo + np.arange(r), k) for (r, k), lo in zip(
            buckets, np.cumsum([0] + [r for r, _ in buckets[:-1]]))])
        row_at = np.argsort(np.asarray(place))              # bucketed position -> row
        pad = cols == rows_t
        assert (vals[pad] == 0).all() and (cols[~pad] < rows_t).all()
        w, piece = np.nonzero(~pad)
        local = cols[w, piece]
        col = hot[local] if is_hot else (j - bool(hot.size)) * table + local
        found.append((row_at[bucketed[piece]], col, vals[w, piece]))
    return [np.concatenate(part) for part in zip(*found)]


LAYOUTS = {  # the operand, HOT_ROWS, tables
    "column_blocks_alone": (lambda: operand(9, 300, 200, 6000, 1500), None, 4),
    "hot_table_symmetric": (lambda: skewed(31, 200, 60000, True), 24, 5),
    "hot_table_of_a_rectangle_s_columns": (lambda: skewed(32, 200, 60000, False), 24, 5),
}


@pytest.mark.parametrize("case", LAYOUTS)
def test_the_layout_holds_every_nonzero_once_in_buckets_of_equal_count(case, sizes):
    make, hot_rows, tables = LAYOUTS[case]
    sizes(1 << 12, 64, hot_rows)
    A, dense = make()
    m, n = dense.shape
    op = sp.prepare(A, symmetric=case == "hot_table_symmetric")
    assert op.tables == len(op.cols) == len(op.buckets) == tables
    table = sp._table(200)                                  # four equal blocks
    assert table == 50
    assert op.hot.shape == ((hot_rows,) if hot_rows else (0,))
    for cols, vals, place, buckets in zip(op.cols, op.vals, op.place, op.buckets):
        assert cols.shape == vals.shape == (sp.PIECE, sum(r * k for r, k in buckets))
        assert sum(r for r, _ in buckets) == m              # every row in one bucket
        assert [k for _, k in buckets] == sorted({k for _, k in buckets})
        assert set(k for _, k in buckets) <= set(sp._counts(1 << 20))
        assert sorted(np.asarray(place)) == list(range(m))
    # every nonzero of the matrix in exactly one slot of one table
    r, c, v = nonzeros_of(op)
    assert r.size == A.nse == int((dense != 0).sum())
    assert np.unique(r * n + c).size == r.size
    np.testing.assert_array_equal(v, dense[r, c])
    slots = sum(c.size for c in op.cols)
    assert slots <= 1.25 * A.nse + sp.PIECE * m * len(op.cols)
    if hot_rows:
        hot = np.asarray(op.hot)
        count = (dense != 0).sum(0)                         # the columns' own counts
        assert (np.diff(hot) > 0).all()
        assert count[hot].min() >= np.delete(count, hot).max()
        assert op.hot_nse == int(count[hot].sum()) == int((np.isin(c, hot)).sum())
        assert op.hot_share == op.hot_nse / A.nse > 0.2
    else:
        assert op.hot_nse == 0 and op.hot_share == 0.0


HOT = {  # n, draws, symmetric, order, chunk bytes (of 5 f32 columns), table rows, hot rows
    "symmetric_sorted": (200, 60000, True, "sorted", 1 << 12, 64, 24),
    "symmetric_shuffled": (200, 60000, True, "shuffled", 1 << 12, 64, 24),
    "columns_counted_not_rows": (200, 60000, False, "shuffled", 1 << 12, 64, 24),
    "columns_counted_sorted_by_row": (200, 60000, False, "sorted", 1 << 12, 64, 24),
    "one_cold_table_one_step": (300, 60000, True, "sorted", 1 << 26, 3 << 19, 40),
    "hot_rows_no_power_of_two": (257, 40000, True, "shuffled", 1 << 11, 100, 37),
}


@pytest.mark.parametrize("seed", [41, 42])
@pytest.mark.parametrize("case", HOT)
def test_products_through_a_hot_table_match_the_dense_matrix(case, seed, sizes):
    n, draws, symmetric, order, chunk_bytes, table, hot_rows = HOT[case]
    sizes(chunk_bytes, table, hot_rows)
    A, dense = skewed(seed, n, draws, symmetric, order)
    op = sp.prepare(A, symmetric=symmetric)
    assert op.hot.shape == (hot_rows,) and op.tables == 1 + -(-n // table)
    assert 0.2 < op.hot_share < 1
    Y = np.random.default_rng(seed + 1).normal(size=(n, 5)).astype(F32)
    want = dense.astype(np.float64) @ Y
    bound = 4e-7 * (np.abs(dense).astype(np.float64) @ np.abs(Y)).max() + 1e-7
    got = sp.spmm(op, Y)
    assert got.dtype == jnp.float32 and np.abs(np.asarray(got) - want).max() <= 16 * bound
    y = sp.spmm(op, Y[:, 0])                                 # a vector
    np.testing.assert_array_equal(y, sp.spmm(op, Y[:, :1])[:, 0])
    assert np.abs(np.asarray(y) - want[:, 0]).max() <= 16 * bound
    if symmetric:                                            # Aᵀ·Y from the one layout
        np.testing.assert_array_equal(sp.spmm(op, Y, transpose=True), got)
    else:                                                    # Aᵀ's own: its columns are A's rows, flat
        assert np.abs(np.asarray(sp.spmm(sp.prepare(A.T), Y)) - dense.T.astype(np.float64) @ Y
                      ).max() <= 16 * bound
    np.testing.assert_allclose(jax.jit(sp.spmm)(op, Y), got, rtol=2e-5, atol=2e-5)


def layout_crc(op):
    import zlib

    crc = 0
    for a in (*op.cols, *op.vals, *op.place):
        crc = zlib.crc32(np.ascontiguousarray(np.asarray(a)).tobytes(), crc)
    return crc


KEPT = {  # the operand, symmetric, HOT_ROWS, the crc32 of the layout at commit 92917cb
    "random_columns_past_hot_rows": (lambda: operand(9, 300, 200, 6000, 1500), False, 24, 1551483926),
    "flat_symmetric_past_hot_rows": (lambda: skewed(33, 200, 5000, True, exponent=0.0), True, 24, 2481639418),
    "skewed_but_within_hot_rows": (lambda: skewed(31, 200, 60000, True), True, 200, None),
    "skewed_with_the_modules_own_hot_rows": (lambda: skewed(31, 200, 60000, True), True, None, None),
}


@pytest.mark.parametrize("case", KEPT)
def test_an_operand_the_hot_table_does_not_pay_for_keeps_the_layout_it_had(case, sizes):
    """Flat column counts leave the hot columns ``HOT_ROWS / n`` of the
    nonzeros, too few to pay for a table; ``n <= HOT_ROWS`` is one fast
    table as it is.  Both get the layout of the commit before the hot
    table, to the bit (its crc32 was taken there)."""
    make, symmetric, hot_rows, crc = KEPT[case]
    A, dense = make()
    sizes(1 << 12, 64)                                       # HOT_ROWS past every operand here
    had = sp.prepare(A, symmetric=symmetric)
    sizes(1 << 12, 64, hot_rows)
    op = sp.prepare(A, symmetric=symmetric)
    assert op.hot.shape == (0,) and op.hot_nse == 0 and op.hot_share == 0.0
    assert op.tables == had.tables == 4 and op.buckets == had.buckets
    for a, b in zip((*op.cols, *op.vals, *op.place), (*had.cols, *had.vals, *had.place)):
        np.testing.assert_array_equal(a, b)
    if crc is not None:
        assert layout_crc(op) == crc
    else:                                                    # and skewed enough to get one when it pays
        sizes(1 << 12, 64, 24)
        assert sp.prepare(A, symmetric=symmetric).hot.shape == (24,)


def test_the_hot_table_is_weighed_by_what_its_nonzeros_save(sizes):
    """(ii) of the rule: nonzeros in the hot columns x (COLD_NS - HOT_NS)
    against HOT_MARGIN x rows x (PLACE_NS + PIECE x HOT_NS)."""
    sizes(1 << 12, 64, 24)
    A, dense = skewed(35, 200, 60000, True)
    count = np.sort((dense != 0).sum(0))[::-1]
    saved = count[:24].sum() * (sp.COLD_NS - sp.HOT_NS)
    paid = 200 * (sp.PLACE_NS + sp.PIECE * sp.HOT_NS)
    assert saved > sp.HOT_MARGIN * paid                     # so it engages ...
    assert sp.prepare(A, symmetric=True).hot.shape == (24,)
    few = jsparse.BCOO.fromdense(jnp.asarray(np.where(                    # ... and with a tenth of the
        np.random.default_rng(1).random(dense.shape) < 0.1, dense, 0)))   # nonzeros it does not
    count = np.sort((np.asarray(few.todense()) != 0).sum(0))[::-1]
    assert count[:24].sum() * (sp.COLD_NS - sp.HOT_NS) < sp.HOT_MARGIN * paid
    assert sp.prepare(few).hot.shape == (0,)
    assert (sp.HOT_ROWS, sp.TABLE_ROWS) == (24, 64)
    with pytest.raises(ValueError, match="square"):
        sp.prepare(jsparse.BCOO.fromdense(jnp.ones((3, 4))), symmetric=True)


def test_the_clamped_last_step_gives_the_bits_of_steps_that_divide_the_pieces(sizes):
    """100 rows of 16 nonzeros: 200 pieces in one table.  Steps of 8
    pieces divide them; steps of 16, 32 and 64 do not, so the last starts
    where a whole step still fits and sums the pieces it shares with the
    step before again: the same bits, a piece's sum reads its own slots.
    (One fold over all pieces, outside any loop, is another program:
    XLA:CPU contracts its multiply-adds differently, so that one is
    compared to rounding.)"""
    rng = np.random.default_rng(38)
    dense = np.zeros((100, 40), F32)
    for r in range(100):
        dense[r, rng.choice(40, 16, replace=False)] = rng.normal(size=16)
    A = jsparse.BCOO.fromdense(jnp.asarray(dense))
    Y = rng.normal(size=(40, 5)).astype(F32)
    sizes(1 << 11, 64)
    op = sp.prepare(A)
    assert [c.shape for c in op.cols] == [(sp.PIECE, 200)] and op.buckets == (((100, 2),),)
    assert sp._chunk(5, 4) // sp.PIECE == 8 and sp.edge_chunks(op, 5) == 25
    divided = np.asarray(sp.spmm(op, Y))
    for chunk_bytes, steps in ((1 << 12, 13), (1 << 13, 7), (1 << 14, 4)):
        sizes(chunk_bytes, 64)
        assert sp.edge_chunks(op, 5) == steps and 200 % (sp._chunk(5, 4) // sp.PIECE)
        np.testing.assert_array_equal(np.asarray(sp.spmm(op, Y)), divided)
    sizes(1 << 26, 64)
    assert sp.edge_chunks(op, 5) == 1                        # fewer pieces than a step: one fold
    np.testing.assert_allclose(sp.spmm(op, Y), divided, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("hot_rows", [None, 24])
def test_every_step_size_gives_the_same_bits_table_by_table(hot_rows, sizes):
    """Hardly a table's pieces are a multiple of a step: the last step of
    almost every table is clamped, the hot one's too."""
    A, dense = skewed(36, 200, 60000, True)
    Y = np.random.default_rng(37).normal(size=(200, 5)).astype(F32)
    sizes(1 << 11, 64, hot_rows)
    op = sp.prepare(A, symmetric=True)
    assert op.tables == (5 if hot_rows else 4)
    first = np.asarray(sp.spmm(op, Y))
    for chunk_bytes in (1 << 12, 3000, 1 << 13):             # 16, 16, 32 pieces a step
        sizes(chunk_bytes, 64, hot_rows)
        step = sp._chunk(5, 4) // sp.PIECE
        assert all(c.shape[1] > step for c in op.cols)
        assert sum(c.shape[1] % step > 0 for c in op.cols) >= op.tables - 1
        assert sp.edge_chunks(op, 5) == sum(-(-c.shape[1] // step) for c in op.cols)
        np.testing.assert_array_equal(np.asarray(sp.spmm(op, Y)), first)


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
