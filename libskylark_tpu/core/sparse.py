"""The sparse-times-panel product: ``A·Y`` and ``Aᵀ·Y`` for a sparse ``A``
and a dense panel ``Y`` (≙ the mixed sparse × dense ``base::Gemm``
overloads, ``base/Gemm.hpp``, that upstream's sparse half rides on).

``jax.experimental.sparse``'s own product (``bcoo_dot_general``) gathers
one row of ``Y`` for every nonzero into one ``nnz × s`` array and
scatter-adds that: 15 GB beside a 2.8 GB operand at 2.3 × 10⁸ nonzeros
and s = 16, and a scatter of rows is the slowest thing a TPU does (a v5e
scatter-adds a 16-column f32 row in 100 ns).  :func:`spmm` scatters
nothing and walks the nonzeros in fixed-size chunks inside the program,
so the gathered rows that are live at once are a chunk's, ``chunk × s``;
the chunk is chosen from what the call can see (the panel's width and
dtype), no setting names it.  All of it lies under the named scope
``sparse.product``.

The operand is a :class:`Prepared`: built once from the BCOO by
:func:`prepare`, outside any timed call, and handed to the programs as
an argument like any array.  It holds the nonzeros in the order a TPU
multiplies them fast in, every gather from a table small enough for one
of the chip's faster gathers.  A plain BCOO is not taken: it keeps going through
``A @ Y`` where the callers had it (``linalg/svd.py``,
``sketch/dense.py``, ``solvers/krylov.py``).

What a product holds besides the operand and the panels: a chunk's
gathered rows, and the pieces' sums of a table, one ``s``-row for every
``PIECE`` slots.  The second is the larger and grows with nnz: an eighth
of ``slots × s``, 0.51 GB for the largest of three tables where
``nnz × s`` is 9.0 GB, at 1.4 × 10⁸ nonzeros and s = 16 (the TPU compiler
may keep more than one table's at once: docs/performance.md has its
counts).

``TABLE_ROWS``, ``HOT_ROWS``, ``PIECE``, ``CHUNK_BYTES`` and the three
rates ``prepare`` weighs a hot table with are constants measured on one
chip at one width, a v5e gathering 16-column f32 rows (64 bytes: a row of
a table of at most 196 608 rows in 2.7 ns, of
524 288 to 1 572 864 rows in 6.5 ns, of 2 097 152 rows and more in
22.6 ns; PERF.md section 6, PR 37 and 38).  The layout is built before
any panel is seen, so it cannot follow the panel's width: a wider panel,
another dtype or another chip gets these tables, at a cost nobody has
measured.

The layout.  The columns are cut into the fewest equal blocks of at most
``TABLE_ROWS``; a block's rows of ``Y`` are the table its nonzeros gather
from.  Where it pays, the ``HOT_ROWS`` columns with the most nonzeros are
taken out of their blocks into a *hot table* before them, ``Y[hot]``,
small enough for the chip's fastest gather: a graph's hubs are few and
hold a third of its nonzeros and more.  ``prepare`` decides that from
the operand's own column counts and no setting does (:func:`_hot_columns`
has the rule): an operand of at most ``HOT_ROWS`` columns, or one whose
counts are flat, keeps column blocks alone, the layout it had before
there was a hot table, to the bit.  Within a table every row's nonzeros
are cut into *pieces* of ``PIECE`` slots (the last padded with a slot
that reads the zero row), rows are sorted by their number of pieces and
grouped into buckets of equal count (counts are rounded up to 1 ... 8,
10, 12, 14, 16, 20, ...: four to the octave, a sixth more slots than
nonzeros at a mean degree of 76 in three tables), and a bucket's pieces
are laid out piece-major.  So the whole table is one ``(PIECE, pieces)``
array of local column indices and one of values; the product gathers
``PIECE`` panels of rows from the table and adds them up (a piece's
sum), ``CHUNK_BYTES`` of gathered rows a step in one loop a table (the
last step starts where a whole step still fits, so no second copy of the
step stands behind the loop for the pieces left over), a bucket's row
sums are one dense ``reshape(count, rows, s).sum(0)``, and the rows go
back to their places by one gather a table.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import sparse as jsparse

__all__ = ["spmm", "prepare", "Prepared", "edge_chunks", "panels", "Panels",
           "RowSlots", "row_slots", "panel_rows", "SLOT_MAX", "LINE_SLOTS",
           "CHUNK_BYTES", "PIECE", "TABLE_ROWS", "HOT_ROWS", "SCOPE"]

SCOPE = "sparse.product"

# The gathered rows of one chunk, ``chunk × s`` in the accumulator's
# dtype, take about this much.
CHUNK_BYTES = 1 << 26
# Slots of a piece: the sublanes of one f32 tile.
PIECE = 8
# Rows of Y that one block of columns gathers from: the largest table
# (of 16 f32 columns) that a v5e still gathers from at 6.5 ns a row.
TABLE_ROWS = 3 << 19
# Columns of the hot table: a v5e gathers from a table of so many rows
# (and the zero row) at 2.7 ns a row, as from any of up to 196,608; from
# 262,144 at 10.8.
HOT_ROWS = 1 << 17
# What ``prepare`` weighs a hot table with, ns a row of 16 f32 columns on
# a v5e (docs/performance.md, "The sparse-times-panel product"): eight
# gathers a step of a loop from a table of HOT_ROWS rows and from one of
# up to TABLE_ROWS, and the gather that puts a table's rows back.
HOT_NS, COLD_NS, PLACE_NS = 2.71, 6.45, 12.0
# The hot table is built where what its nonzeros save is at least this
# many times what the table costs.
HOT_MARGIN = 2.0


def _chunk(s: int, itemsize: int) -> int:
    """Nonzeros (slots) a step: the power of two whose gathered rows
    take ``CHUNK_BYTES``."""
    fit = max(CHUNK_BYTES // (max(s, 1) * itemsize), PIECE)
    return 1 << (fit.bit_length() - 1)


def _table(n: int) -> int:
    """Rows of the table one block of columns gathers from: ``n`` columns
    cut into the fewest blocks of at most ``TABLE_ROWS``, all as large
    (the last may lack a few rows), so that no block is left a table in
    a regime of its own."""
    blocks = -(-max(n, 1) // TABLE_ROWS)
    return -(-max(n, 1) // blocks)


def edge_chunks(A: "Prepared", s: int) -> int:
    """Steps that one product of ``A`` with an ``s``-column f32 panel
    walks the nonzeros in."""
    chunk = _chunk(s, 4)
    return sum(-(-c.shape[1] * PIECE // chunk) for c in A.cols)


# -- the prepared operand -----------------------------------------------------


def _counts(limit: int):
    """The piece counts a bucket may have, up to ``limit``: 1 to 8, then
    four to the octave (10, 12, 14, 16, 20, ...): a row is padded by at
    most a quarter of its pieces, an eighth on average."""
    out, step = list(range(1, 9)), 2
    while out[-1] < limit:
        top = out[-1]
        out.extend(top + step * i for i in range(1, 5))
        step *= 2
    return out


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class Prepared:
    """A sparse matrix in the product's own layout (module docstring).

    ``cols[j]``, ``vals[j]``: the ``(PIECE, pieces_j)`` local column
    indices (``table rows`` for a padding slot) and values of table j;
    ``place[j]``: for every row of the matrix, its position among table
    j's bucketed rows; ``hot``: the columns of the hot table, ascending
    (table 0 is then ``Y[hot]``, the others the column blocks in order),
    empty where there is none.  Static: ``shape``, ``nse``, ``hot_nse``
    (nonzeros that gather from the hot table), ``buckets[j]`` (``(rows,
    count)`` of every bucket of table j, in layout order), ``symmetric``
    (the matrix equals its transpose, so ``transpose=True`` is the same
    product)."""

    cols: tuple
    vals: tuple
    place: tuple
    hot: jax.Array
    shape: tuple
    nse: int
    hot_nse: int
    buckets: tuple
    symmetric: bool

    def tree_flatten(self):
        return ((self.cols, self.vals, self.place, self.hot),
                (self.shape, self.nse, self.hot_nse, self.buckets,
                 self.symmetric))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def dtype(self):
        return self.vals[0].dtype

    @property
    def tables(self) -> int:
        """Tables the nonzeros gather from, the hot one included."""
        return len(self.cols)

    @property
    def hot_share(self) -> float:
        """The share of the nonzeros that gather from the hot table."""
        return self.hot_nse / self.nse if self.hot_nse else 0.0


@jax.jit
def _by_block_and_row(key, col, val):
    return lax.sort((key, col, val), num_keys=1)


@jax.jit
def _keys(rows, cols, rank, m, n, table, tables):
    """The sort key (``table · m + row``; past all ``tables`` for an
    index past the shape, which pads a BCOO) and the local column of
    every nonzero.  ``rank``: every column's row in the hot table or -1,
    or None where there is no hot table."""
    live = (rows < m) & (cols < n)
    block, col = cols // table, cols % table
    if rank is not None:
        r = rank.at[cols].get(mode="clip")
        block, col = jnp.where(r < 0, 1 + block, 0), jnp.where(r < 0, col, r)
    return jnp.where(live, block * m + rows, tables * m), col


@jax.jit
def _fill(base, left, col, val, pad):
    """The ``(PIECE, pieces)`` arrays of one table: slot w of a piece is
    nonzero ``base + w`` while ``w < left``, a padding slot after."""
    w = jnp.arange(PIECE, dtype=jnp.int32)[:, None]
    at = jnp.minimum(base[None, :] + w, col.shape[0] - 1)
    live = w < left[None, :]
    return (jnp.where(live, col[at], pad),
            jnp.where(live, val[at], jnp.zeros((), val.dtype)))


def _hot_columns(A, symmetric: bool):
    """The columns whose nonzeros gather from the hot table, ascending:
    the ``HOT_ROWS`` of largest count, or none.  None where the columns
    are one fast table as they are (``n <= HOT_ROWS``), and none unless
    what the hot columns' nonzeros save, gathered at ``HOT_NS`` and not
    ``COLD_NS``, is ``HOT_MARGIN`` times what one more table costs: its
    rows put back (``m`` at ``PLACE_NS``) and its padding (up to a piece
    a row, at ``HOT_NS``).  Flat counts leave the hot columns ``HOT_ROWS
    / n`` of the nonzeros, which clears that only where n is near
    ``HOT_ROWS`` or the rows are long; the hubs of a degree-skewed graph
    hold a third of them and more.

    The counts are the rows' for a symmetric matrix; else the nonzeros
    are sorted by column with the layout's own sort program, at its
    shapes (a sort of a new shape is a minute of the TPU compiler)."""
    m, n = A.shape
    none = np.zeros((0,), np.int32)
    if n <= HOT_ROWS:
        return none
    by = A.indices[:, 0 if symmetric else 1]
    if not (symmetric and A.indices_sorted):
        by = _by_block_and_row(by, A.indices[:, 1], A.data)[0]
    edge = jnp.searchsorted(by, jnp.arange(n + 1, dtype=jnp.int32), side="left")
    count = np.diff(np.asarray(edge).astype(np.int64))
    hot = np.argsort(-count, kind="stable")[:HOT_ROWS]
    saved = count[hot].sum() * (COLD_NS - HOT_NS)
    paid = m * (PLACE_NS + PIECE * HOT_NS)
    return np.sort(hot).astype(np.int32) if saved >= HOT_MARGIN * paid else none


def prepare(A, *, symmetric: bool = False) -> Prepared:
    """``A`` (a two-dimensional BCOO) in the product's layout.
    ``symmetric=True`` is the caller's word that ``A = Aᵀ`` (an
    undirected graph's adjacency): the one layout then serves both
    products; without it the prepared operand multiplies as ``A·Y``
    alone.  Costs a sort of the nonzeros (none for a BCOO sorted
    by row with one table), a search for every row's start and
    two passes of scalar gathers over the nonzeros on the device, and the
    bucketing of the row counts (``rows × tables`` integers) on the host;
    with more than ``HOT_ROWS`` columns also their counts (a search; a
    sort before it unless the matrix is symmetric and sorted by row) and,
    where a hot table is built, one more scalar gather: seconds at 10⁸
    nonzeros, to be paid where the operand is made, never inside a
    solve."""
    if A.n_batch or A.n_dense or A.ndim != 2:
        raise ValueError(f"prepare takes a plain 2-D BCOO, got {A}")
    m, n = A.shape
    if symmetric and m != n:
        raise ValueError(f"a symmetric matrix is square, got {m}x{n}")
    nse = A.nse
    table = _table(n)
    hot = _hot_columns(A, symmetric)
    # a padding slot reads the row after the table's last
    pads = [hot.size] * bool(hot.size) + [table] * -(-n // table)
    blocks = len(pads)
    if blocks * m >= 2**31:
        raise ValueError(f"{blocks} tables of {m} rows: past int32")
    rank = None
    if hot.size:
        rank = np.full(n, -1, np.int32)
        rank[hot] = np.arange(hot.size, dtype=np.int32)
    key, col = _keys(A.indices[:, 0], A.indices[:, 1], rank, m, n, table, blocks)
    val = A.data
    if blocks > 1 or not A.indices_sorted:
        # (one table of a BCOO sorted by row is in the layout's order already)
        key, col, val = _by_block_and_row(key, col, val)
    if not nse:  # nothing to point a slot at: one zero to read
        col, val = jnp.zeros((1,), col.dtype), jnp.zeros((1,), val.dtype)
    start = np.asarray(jnp.searchsorted(
        key, jnp.arange(blocks * m + 1, dtype=jnp.int32), side="left"))
    start = start.astype(np.int64)
    deg = np.diff(start).reshape(blocks, m)
    start = start[:-1].reshape(blocks, m)

    out_cols, out_vals, out_place, out_buckets = [], [], [], []
    for j in range(blocks):
        need = np.maximum(-(-deg[j] // PIECE), 1)
        counts = np.asarray(_counts(int(need.max())))
        count = counts[np.searchsorted(counts, need)]
        order = np.argsort(count, kind="stable")
        place = np.empty(m, np.int64)
        place[order] = np.arange(m)
        edges = np.searchsorted(count[order], counts, side="left")
        base, left, buckets = [], [], []
        for k, lo, hi in zip(counts, edges, list(edges[1:]) + [m]):
            if hi == lo:
                continue
            who = order[lo:hi]
            step = (np.arange(k, dtype=np.int64) * PIECE)[:, None]
            base.append((start[j, who][None, :] + step).ravel())
            left.append((deg[j, who][None, :] - step).ravel())
            buckets.append((int(hi - lo), int(k)))
        base, left = np.concatenate(base), np.concatenate(left)
        cj, vj = _fill(jnp.asarray(base, jnp.int32),
                       jnp.asarray(np.clip(left, 0, PIECE), jnp.int32),
                       col, val, jnp.int32(pads[j]))
        out_cols.append(cj)
        out_vals.append(vj)
        out_place.append(jnp.asarray(place, jnp.int32))
        out_buckets.append(tuple(buckets))
    return Prepared(tuple(out_cols), tuple(out_vals), tuple(out_place),
                    jnp.asarray(hot), (m, n), int(nse),
                    int(deg[0].sum()) if hot.size else 0,
                    tuple(out_buckets), bool(symmetric))


def _prepared_product(A: Prepared, Y, acc):
    m, n = A.shape
    s = Y.shape[1]
    table = _table(n)
    step = _chunk(s, jnp.dtype(acc).itemsize) // PIECE
    is_hot = bool(A.hot.shape[0])
    out = jnp.zeros((m, s), acc)
    for j, (cols, vals, place, buckets) in enumerate(
            zip(A.cols, A.vals, A.place, A.buckets)):
        # the table's rows of Y, then zero rows: the first is the padding
        # slots' (the last column block may lack a few rows)
        if is_hot and j == 0:
            T, size = Y[A.hot], A.hot.shape[0]
        else:
            at = (j - is_hot) * table
            T, size = lax.slice_in_dim(Y, at, min(at + table, n), axis=0), table
        T = jnp.concatenate([T, jnp.zeros((size + 1 - T.shape[0], s), acc)])
        pieces = cols.shape[1]

        def fold(S, start, size, cols=cols, vals=vals, T=T):
            c = lax.dynamic_slice_in_dim(cols, start, size, axis=1)
            v = lax.dynamic_slice_in_dim(vals, start, size, axis=1)
            part = sum(T[c[w]] * v[w].astype(acc)[:, None]
                       for w in range(PIECE))
            return lax.dynamic_update_slice_in_dim(S, part, start, axis=0)

        S = jnp.zeros((pieces, s), acc)
        if pieces <= step:
            S = fold(S, 0, pieces)
        else:
            # no remainder: the last step starts where a whole step still
            # fits, and writes the pieces it shares with the one before
            # again (the same bits: a piece's sum reads its own slots)
            S = lax.fori_loop(
                0, -(-pieces // step),
                lambda i, S: fold(
                    S, jnp.minimum(i * step, pieces - step), step), S)
        sums, at = [], 0
        for rows_b, k in buckets:
            sums.append(lax.slice_in_dim(S, at, at + rows_b * k, axis=0)
                        .reshape(k, rows_b, s).sum(0))
            at += rows_b * k
        out = out + jnp.concatenate(sums)[place]
    return out


# -- the product --------------------------------------------------------------


@partial(jax.jit, static_argnames=("transpose",))
def spmm(A, Y, *, transpose: bool = False):
    """``A·Y`` for a :class:`Prepared` ``A`` and a dense ``Y`` with as
    many rows as ``A`` has columns (a panel, or a vector); ``Aᵀ·Y`` with
    ``transpose``, which an operand prepared with ``symmetric=True``
    serves as the same product and any other refuses (``prepare(A.T)``
    holds Aᵀ's layout).  Accumulated in f32 (f64 where the operands are),
    returned in the operands' common dtype.  A program of its own where
    it is called op by op (built once a shape), part of the caller's
    under a ``jit``."""
    if not isinstance(A, Prepared):
        raise TypeError(
            f"spmm takes a prepared operand (core.sparse.prepare), got "
            f"{type(A).__name__}")
    if transpose and not A.symmetric:
        raise ValueError(
            "a prepared operand multiplies as A·Y alone unless it was "
            "prepared with symmetric=True; prepare(A.T) holds Aᵀ's layout")
    Y = jnp.asarray(Y)
    if Y.ndim == 1:
        return spmm(A, Y[:, None], transpose=transpose)[:, 0]
    m, n = A.shape
    if Y.ndim != 2 or Y.shape[0] != n:
        raise ValueError(
            f"spmm: {'Aᵀ' if transpose else 'A'} is {m}x{n}, Y is {Y.shape}")
    dtype = jnp.result_type(A.dtype, Y.dtype)
    acc = jnp.promote_types(dtype, jnp.float32)
    with jax.named_scope(SCOPE):
        return _prepared_product(A, Y.astype(acc), acc).astype(dtype)


# -- row slots and panels -------------------------------------------------------

# The most slots a row holds in place; its further nonzeros go to overflow
# rows.  The one-hot products that hash a row's slots
# (``HashSketch._rows_dense_out``) were measured on a v5e at this width.
SLOT_MAX = 128
# What a line of slots costs besides its slots, in slots: its S bins.  A
# v5e made 45,208 lines of 128 slots into S = 4096 f32 bins in 5.3 ms, of
# which writing the bins at HBM rate is about 0.9 ms: a sixth, some 27
# slots a line.  An overflow line's bins are read and added again.
LINE_SLOTS = 32


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class RowSlots:
    """An ``m × n`` sparse matrix as K slots a row.  ``data`` and
    ``cols``, ``(m, K)``: row r's first nonzeros (at most K) in its first
    slots, the other slots padding (value 0, column 0).  A row of more
    than K nonzeros goes on in overflow rows: ``xdata`` and ``xcols``,
    ``(E, K)``, hold K of them at a time, ``xrows`` ``(E,)`` the row each
    belongs to, in ascending order (padding rows: the last row, value
    0).  Padding adds nothing wherever it lands.  Static: ``shape``.
    :func:`panels` makes them a panel at a time, :func:`row_slots` for a
    whole BCOO."""

    data: jax.Array
    cols: jax.Array
    xdata: jax.Array
    xcols: jax.Array
    xrows: jax.Array
    shape: tuple

    ndim = 2

    def tree_flatten(self):
        return (self.data, self.cols, self.xdata, self.xcols, self.xrows), (self.shape,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def dtype(self):
        return self.data.dtype

    def astype(self, dtype):
        return RowSlots(self.data.astype(dtype), self.cols, self.xdata.astype(dtype),
                        self.xcols, self.xrows, self.shape)


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class Panels:
    """A sparse matrix cut into row panels of one count of slots each, for
    a consumer that walks them one at a time (:func:`panels`;
    ``ml.streaming_kernel_ridge`` with :func:`panel_rows`): the arrays of
    :class:`RowSlots` with a leading panel axis, ``data`` and ``cols``
    ``(panels, rows, K)``, ``xdata`` and ``xcols`` ``(panels, E, K)``,
    ``xrows`` ``(panels, E)`` (rows local to their panel).  Row r of
    panel p is row ``p · rows + r`` of the matrix.  Static: ``shape``
    and ``nse``, the nonzeros (padding not counted)."""

    data: jax.Array
    cols: jax.Array
    xdata: jax.Array
    xcols: jax.Array
    xrows: jax.Array
    shape: tuple
    nse: int

    def tree_flatten(self):
        return ((self.data, self.cols, self.xdata, self.xcols, self.xrows),
                (self.shape, self.nse))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def rows(self) -> int:
        """Rows of a panel."""
        return self.data.shape[1]

    def panel(self, p) -> RowSlots:
        """Panel ``p`` (a traced index too) as :class:`RowSlots`."""
        at = partial(lax.dynamic_index_in_dim, index=p, axis=0, keepdims=False)
        return RowSlots(at(self.data), at(self.cols), at(self.xdata), at(self.xcols),
                        at(self.xrows), (self.rows, self.shape[1]))


def _triplets(A, shape):
    """(row, col, val, (m, n)) of ``A`` on the host, sorted by row."""
    if isinstance(A, jsparse.BCOO):
        if A.n_batch or A.n_dense or A.ndim != 2:
            raise ValueError(f"panels take a plain 2-D BCOO, got {A}")
        shape, ordered = A.shape, A.indices_sorted
        data, indices = A.data, A.indices
    else:
        (data, indices), ordered = A, False
        if shape is None:
            raise ValueError("host triplets need their shape")
    m, n = (int(x) for x in shape)
    idx, val = np.asarray(indices), np.asarray(data)
    row, col = idx[:, 0].astype(np.int64), idx[:, 1].astype(np.int32)
    live = (row < m) & (col < n)  # an index past the shape pads a BCOO
    if not live.all():
        row, col, val = row[live], col[live], val[live]
    if not ordered and row.size and (np.diff(row) < 0).any():
        order = np.argsort(row, kind="stable")
        row, col, val = row[order], col[order], val[order]
    return row, col, val, (m, n)


def _slot_width(count, rows: int):
    """(K, E): the slots a row holds in place (a multiple of 8, at most
    ``SLOT_MAX``) and the overflow rows a panel (the most any panel needs,
    rounded up to 8), chosen for the least work a panel: its ``rows + E``
    lines of K slots and ``LINE_SLOTS`` for each line's bins, twice more
    for an overflow line's; of equals, the widest K."""
    top = min(SLOT_MAX, max(-(-int(count.max(initial=0)) // 8) * 8, 8))
    per_panel = count.reshape(-1, rows)
    best = None
    for k in range(8, top + 1, 8):
        extra = np.maximum(-(-per_panel // k) - 1, 0).sum(1)
        e = -(-int(extra.max(initial=0)) // 8) * 8
        work = (rows + e) * (k + LINE_SLOTS) + 2 * e * LINE_SLOTS
        if best is None or work <= best[0]:
            best = (work, k, e)
    return best[1], best[2]


def _lines(start, count, col, val, k, out):
    """Slot j of line i is nonzero ``start[i] + j`` while ``j < count[i]``,
    padding after; written into ``out``'s (values, columns) rows."""
    j = np.arange(k)
    live = j < count[:, None]
    at = np.where(live, start[:, None] + j, 0)
    out[0][...] = np.where(live, val[at], np.zeros((), val.dtype))
    out[1][...] = np.where(live, col[at], 0)


def panels(A, rows: int, *, shape=None) -> Panels:
    """``A`` (``m × n``, ``m`` a multiple of ``rows``) as :class:`Panels`
    of ``rows`` rows.  A row keeps its first K nonzeros in place and the
    rest in overflow rows, K at a time; every panel holds ``rows + E``
    lines of K slots, K and E chosen for the least work
    (:func:`_slot_width`), so what a pass reads grows with the nonzeros
    and not with the longest row.  ``A``: a plain 2-D BCOO, or the same
    triplets on the host, ``(data, indices)`` numpy arrays with
    ``shape``.  Laid out on the host (a sort by row unless the triplets
    are sorted), the slots then put on the device: to be paid where the
    operand is made, outside any timed call."""
    row, col, val, (m, n) = _triplets(A, shape)
    if rows < 1 or m % rows:
        raise ValueError(f"{m} rows do not cut into panels of {rows}")
    count = np.bincount(row, minlength=m)
    start = np.concatenate([[0], np.cumsum(count)[:-1]])
    if not col.size:  # every slot padding; something to read it from
        col, val = np.zeros(1, np.int32), np.zeros(1, val.dtype)
    k, e = _slot_width(count, rows)
    nb = m // rows
    data = np.empty((nb, rows, k), val.dtype)
    cols = np.empty((nb, rows, k), np.int32)
    xdata = np.zeros((nb, e, k), val.dtype)
    xcols = np.zeros((nb, e, k), np.int32)
    xrows = np.full((nb, e), rows - 1, np.int32)
    for p in range(nb):
        cnt, st = count[p * rows:(p + 1) * rows], start[p * rows:(p + 1) * rows]
        _lines(st, cnt, col, val, k, (data[p], cols[p]))
        pieces = np.maximum(-(-cnt // k) - 1, 0)
        owner = np.repeat(np.arange(rows), pieces)
        # the piece's place within its row: 1, 2, ... after the row's first K
        nth = 1 + np.arange(owner.size) - np.repeat(np.cumsum(pieces) - pieces, pieces)
        x = owner.size
        _lines(st[owner] + nth * k, cnt[owner] - nth * k, col, val, k,
              (xdata[p, :x], xcols[p, :x]))
        xrows[p, :x] = owner
    return Panels(jnp.asarray(data), jnp.asarray(cols), jnp.asarray(xdata),
                  jnp.asarray(xcols), jnp.asarray(xrows), (m, n), int(count.sum()))


def row_slots(A, *, shape=None) -> RowSlots:
    """The whole of ``A`` (as :func:`panels` takes it) as one
    :class:`RowSlots`."""
    m = int((A.shape if shape is None else shape)[0])
    return panels(A, m, shape=shape).panel(0)


def panel_rows(start, rows, X: Panels) -> RowSlots:
    """The ``block_fn`` of ``ml.streaming_kernel_ridge`` for a
    :class:`Panels` ``X`` handed to it through ``block_args``: rows
    ``start … start + rows`` (a traced ``start``, a multiple of ``rows``)
    as the :class:`RowSlots` of panel ``start // rows``.  It lives with
    its module, so the trainer's three programs are built once a
    process."""
    if rows != X.rows:
        raise ValueError(f"panels of {X.rows} rows, asked for {rows}")
    return X.panel(start // rows)
