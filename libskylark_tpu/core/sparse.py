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
multiplies them fast in, every gather from a table small enough for the
chip's faster gather.  A plain BCOO is not taken: it keeps going through
``A @ Y`` where the callers had it (``linalg/svd.py``,
``sketch/dense.py``, ``solvers/krylov.py``).

What a product holds besides the operand and the panels: a chunk's
gathered rows, and the pieces' sums of one block of columns, one
``s``-row for every ``PIECE`` slots.  The second is the larger and grows
with nnz: a seventh of the ``nnz × s`` of the block's own nonzeros
(0.63 GB a block, where ``nnz × s`` is 9.0 GB, at 1.4 × 10⁸ nonzeros in
two blocks and s = 16).

``TABLE_ROWS``, ``PIECE`` and ``CHUNK_BYTES`` are constants measured on
one chip at one width, a v5e gathering 16-column f32 rows (64 bytes: a
row of a table of 524 288 to 1 572 864 rows in 6.5 ns, of 2 097 152 rows
and more in 22.6 ns; PERF.md section 6, PR 37).  The layout is built
before any panel is seen, so it cannot follow the panel's width: a wider
panel, another dtype or another chip gets these tables, at a cost nobody
has measured.

The layout.  The columns are cut into the fewest equal blocks of at most
``TABLE_ROWS``; a block's rows of ``Y`` are the table its nonzeros
gather from.  Within a block every row's nonzeros are cut into *pieces*
of ``PIECE`` slots (the last padded with a slot that reads a zero row),
rows are sorted by their number of pieces and grouped into buckets of
equal count (counts are rounded up to 1 ... 8, 10, 12, 14, 16, 20, ...:
four to the octave, a seventh more slots than nonzeros at a mean degree
of 76 in two blocks), and a bucket's pieces are laid out piece-major.
So the whole block is one ``(PIECE, pieces)`` array of local column
indices and one of values; the product gathers ``PIECE`` panels of rows
from the table and adds them up (a piece's sum), a bucket's row sums are
one dense ``reshape(count, rows, s).sum(0)``, and the rows go back to
their places by one gather a block.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["spmm", "prepare", "Prepared", "edge_chunks",
           "CHUNK_BYTES", "PIECE", "TABLE_ROWS", "SCOPE"]

SCOPE = "sparse.product"

# The gathered rows of one chunk, ``chunk × s`` in the accumulator's
# dtype, take about this much.
CHUNK_BYTES = 1 << 26
# Slots of a piece: the sublanes of one f32 tile.
PIECE = 8
# Rows of Y that one block of columns gathers from: the largest table
# (of 16 f32 columns) that a v5e still gathers from at 6.5 ns a row.
TABLE_ROWS = 3 << 19


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
    indices (``table rows`` for a padding slot) and values of column
    block j; ``place[j]``: for every row of the matrix, its position
    among block j's bucketed rows.  Static: ``shape``, ``nse``,
    ``buckets[j]`` (``(rows, count)`` of every bucket of block j, in
    layout order), ``symmetric`` (the matrix equals its transpose, so
    ``transpose=True`` is the same product)."""

    cols: tuple
    vals: tuple
    place: tuple
    shape: tuple
    nse: int
    buckets: tuple
    symmetric: bool

    def tree_flatten(self):
        return ((self.cols, self.vals, self.place),
                (self.shape, self.nse, self.buckets, self.symmetric))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def dtype(self):
        return self.vals[0].dtype


@jax.jit
def _by_block_and_row(key, col, val):
    return lax.sort((key, col, val), num_keys=1)


@jax.jit
def _fill(base, left, col, val, pad):
    """The ``(PIECE, pieces)`` arrays of one block: slot w of a piece is
    nonzero ``base + w`` while ``w < left``, a padding slot after."""
    w = jnp.arange(PIECE, dtype=jnp.int32)[:, None]
    at = jnp.minimum(base[None, :] + w, col.shape[0] - 1)
    live = w < left[None, :]
    return (jnp.where(live, col[at], pad),
            jnp.where(live, val[at], jnp.zeros((), val.dtype)))


def prepare(A, *, symmetric: bool = False) -> Prepared:
    """``A`` (a two-dimensional BCOO) in the product's layout.
    ``symmetric=True`` is the caller's word that ``A = Aᵀ`` (an
    undirected graph's adjacency): the one layout then serves both
    products; without it the prepared operand multiplies as ``A·Y``
    alone.  Costs a sort of the nonzeros (none for a BCOO sorted
    by row with one block of columns), a search for every row's start and
    two passes of scalar gathers over the nonzeros on the device, and the
    bucketing of the row counts (``rows × blocks`` integers) on the host:
    seconds at 10⁸ nonzeros, to be paid where the operand is made, never
    inside a solve."""
    if A.n_batch or A.n_dense or A.ndim != 2:
        raise ValueError(f"prepare takes a plain 2-D BCOO, got {A}")
    m, n = A.shape
    nse = A.nse
    table = _table(n)
    blocks = -(-n // table)
    if blocks * m >= 2**31:
        raise ValueError(f"{blocks} column blocks of {m} rows: past int32")
    rows, cols = A.indices[:, 0], A.indices[:, 1]
    live = (rows < m) & (cols < n)  # an index past the shape pads a BCOO
    key = jnp.where(live, (cols // table) * m + rows, blocks * m)
    col, val = cols % table, A.data
    if blocks > 1 or not A.indices_sorted:
        # (one block of a BCOO sorted by row is in the layout's order already)
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
                       col, val, jnp.int32(table))
        out_cols.append(cj)
        out_vals.append(vj)
        out_place.append(jnp.asarray(place, jnp.int32))
        out_buckets.append(tuple(buckets))
    return Prepared(tuple(out_cols), tuple(out_vals), tuple(out_place),
                    (m, n), int(nse), tuple(out_buckets), bool(symmetric))


def _prepared_product(A: Prepared, Y, acc):
    m, n = A.shape
    s = Y.shape[1]
    table = _table(n)
    step = _chunk(s, jnp.dtype(acc).itemsize) // PIECE
    out = jnp.zeros((m, s), acc)
    for j, (cols, vals, place, buckets) in enumerate(
            zip(A.cols, A.vals, A.place, A.buckets)):
        rows = min(table, n - j * table)
        T = jnp.concatenate([
            lax.slice_in_dim(Y, j * table, j * table + rows, axis=0),
            jnp.zeros((table + 1 - rows, s), acc)])
        pieces = cols.shape[1]

        def fold(S, start, size, cols=cols, vals=vals, T=T):
            c = lax.dynamic_slice_in_dim(cols, start, size, axis=1)
            v = lax.dynamic_slice_in_dim(vals, start, size, axis=1)
            part = sum(T[c[w]] * v[w].astype(acc)[:, None]
                       for w in range(PIECE))
            return lax.dynamic_update_slice_in_dim(S, part, start, axis=0)

        S = jnp.zeros((pieces, s), acc)
        whole = pieces // step
        if whole:
            S = lax.fori_loop(
                0, whole, lambda i, S: fold(S, i * step, step), S)
        if pieces % step:
            S = fold(S, whole * step, pieces % step)
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
