"""Simple undirected graph container built from an arc list.

≙ ``simple_unweighted_graph_t`` (``ml/skylark_graph_se.cpp``) and the
arc-list reader (``utility/io``): text lines ``u v`` (comments ``#``/``%``),
symmetrized, self-loops dropped, duplicate edges collapsed.  Vertex names
may be arbitrary hashables; ``index`` maps name → contiguous id.

The constructor is vectorized: interning runs through one C-speed
``dict.fromkeys`` pass (first-seen order, scanning ``u`` then ``v`` per
edge — identical to the original per-edge loop), and symmetrization /
dedup / CSR assembly are numpy ``unique``/``lexsort``/``bincount`` calls,
so building a multi-hundred-thousand-edge graph costs milliseconds of
interpreter time instead of seconds.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import sparse as jsparse

__all__ = ["SimpleGraph", "read_arc_list", "adjacency_from_edges"]


@jax.jit
def _sort_pairs(a, b):
    """(a, b) sorted by a, then b.  Two keys: ``a * n + b`` does not fit
    32 bits.  One program for both sorts below (a sort of 10⁸ pairs takes
    the TPU's compiler a minute to build, and a moment to run)."""
    return lax.sort((a, b), num_keys=2)


@partial(jax.jit, static_argnames=("n",))
def _both_directions(u, v, *, n: int):
    """(rows, cols): every arc in both directions; a self-loop or an arc
    with an end outside ``[0, n)`` becomes ``(n, n)``, twice."""
    off = (u == v) | (jnp.minimum(u, v) < 0) | (jnp.maximum(u, v) >= n)
    u, v = jnp.where(off, n, u), jnp.where(off, n, v)
    return jnp.concatenate([u, v]), jnp.concatenate([v, u])


@partial(jax.jit, static_argnames=("n",))
def _first_of_each(rows, cols, *, n: int):
    """Sorted pairs with every copy after the first made ``(n, n)``, and
    how many pairs inside the matrix are left."""
    again = jnp.concatenate([jnp.zeros((1,), bool),
                             (rows[1:] == rows[:-1]) & (cols[1:] == cols[:-1])])
    rows, cols = jnp.where(again, n, rows), jnp.where(again, n, cols)
    return rows, cols, jnp.sum(rows < n)


@partial(jax.jit, static_argnames=("nnz",))
def _indices(rows, cols, *, nnz: int):
    return jnp.stack([rows[:nnz], cols[:nnz]], axis=1)


def adjacency_from_edges(u, v, n: int, dtype=jnp.float32):
    """The adjacency matrix of the simple undirected graph on ``n``
    vertices with the arcs ``(u[i], v[i])`` (integer arrays, ids in
    ``[0, n)``): an ``n × n`` BCOO of ones, symmetric, self-loops dropped
    and duplicate edges merged (in either direction), ``int32`` indices
    sorted by row and column and marked so.

    What ``SimpleGraph(zip(u, v)).adjacency_bcoo()`` builds through a
    Python dict and NumPy on the host, for vertices that are already
    integers, in jitted programs that never leave the device: both
    directions of every arc are sorted, the repeats moved to ``(n, n)``,
    and a second sort puts those behind the rest.  The number of nonzeros
    is read once in between: it is a shape."""
    u, v = jnp.asarray(u, jnp.int32), jnp.asarray(v, jnp.int32)
    n = int(n)
    rows, cols = _sort_pairs(*_both_directions(u, v, n=n))
    rows, cols, nnz = _first_of_each(rows, cols, n=n)
    idx = _indices(*_sort_pairs(rows, cols), nnz=int(nnz))
    return jsparse.BCOO(
        (jnp.ones((idx.shape[0],), dtype), idx), shape=(n, n),
        indices_sorted=True, unique_indices=True,
    )


class SimpleGraph:
    def __init__(self, edges):
        """edges: iterable of (u, v) pairs (strings or ints)."""
        # Self-loops drop before interning: a vertex appearing only in
        # self-loops gets no id (pinned by tests).
        pairs = [(u, v) for u, v in edges if u != v]
        flat = [w for pair in pairs for w in pair]
        # dict.fromkeys dedups in insertion order in one C call.
        names = {w: i for i, w in enumerate(dict.fromkeys(flat))}
        self.vertices = list(names)
        self.index = names
        n = len(names)
        self.n = n
        if not pairs:
            self.indptr = np.zeros(n + 1, dtype=np.int64)
            self.indices = np.empty(0, dtype=np.int64)
            return
        ids = np.fromiter(
            (names[w] for w in flat), dtype=np.int64, count=len(flat)
        ).reshape(-1, 2)
        lo = ids.min(axis=1)
        hi = ids.max(axis=1)
        und = np.unique(np.stack([lo, hi], axis=1), axis=0)
        rows = np.concatenate([und[:, 0], und[:, 1]])
        cols = np.concatenate([und[:, 1], und[:, 0]])
        order = np.lexsort((cols, rows))
        rows, cols = rows[order], cols[order]
        counts = np.bincount(rows, minlength=n)
        self.indptr = np.concatenate(
            [np.zeros(1, dtype=np.int64), np.cumsum(counts, dtype=np.int64)]
        )
        self.indices = cols

    def with_edges(self, pairs):
        """New graph absorbing extra edges over the EXISTING vertex set.

        ``pairs``: iterable of (u, v) vertex ids or names.  Returns
        ``(G2, new_pairs)``: the merged graph — same vertex interning,
        same ids, CSR rebuilt — and the (r, 2) int64 array of undirected
        (lo, hi) pairs that were genuinely NEW (self-loops and edges
        already present are dropped, duplicates collapsed).  The live
        serve registry folds exactly ``new_pairs`` into its retained
        adjacency sketch, so the delta fold counts each edge once —
        the same dedup the constructor applies from scratch.

        Vertices must already exist: sketch domains are sized to the
        registered vertex set, so growth is rejected (register with
        isolated capacity vertices if the universe must grow).
        """
        ids = []
        for u, v in pairs:
            iu = u if isinstance(u, (int, np.integer)) else self.index.get(u)
            iv = v if isinstance(v, (int, np.integer)) else self.index.get(v)
            if iu is None or iv is None or not (
                0 <= int(iu) < self.n and 0 <= int(iv) < self.n
            ):
                raise KeyError(
                    f"with_edges: unknown vertex in ({u!r}, {v!r}); live "
                    "folds are over the registered vertex set"
                )
            if int(iu) != int(iv):
                ids.append((int(iu), int(iv)))
        g2 = object.__new__(SimpleGraph)
        g2.vertices = self.vertices
        g2.index = self.index
        g2.n = self.n
        if not ids:
            g2.indptr = self.indptr
            g2.indices = self.indices
            return g2, np.empty((0, 2), np.int64)
        arr = np.asarray(ids, np.int64)
        lo = arr.min(axis=1)
        hi = arr.max(axis=1)
        cand = np.unique(np.stack([lo, hi], axis=1), axis=0)
        # Drop pairs already present (CSR membership on the lo row).
        old_rows = np.repeat(np.arange(self.n, dtype=np.int64),
                             np.diff(self.indptr))
        have = set(zip(old_rows.tolist(), self.indices.tolist()))
        fresh = np.asarray(
            [p for p in cand.tolist() if (p[0], p[1]) not in have], np.int64
        ).reshape(-1, 2)
        if not fresh.size:
            g2.indptr = self.indptr
            g2.indices = self.indices
            return g2, fresh
        rows = np.concatenate([old_rows, fresh[:, 0], fresh[:, 1]])
        cols = np.concatenate([self.indices, fresh[:, 1], fresh[:, 0]])
        order = np.lexsort((cols, rows))
        rows, cols = rows[order], cols[order]
        counts = np.bincount(rows, minlength=self.n)
        g2.indptr = np.concatenate(
            [np.zeros(1, dtype=np.int64), np.cumsum(counts, dtype=np.int64)]
        )
        g2.indices = cols
        return g2, fresh

    # -- accessors (≙ the GraphType concept used by the algorithms) ---------

    def degree(self, i: int) -> int:
        return int(self.indptr[i + 1] - self.indptr[i])

    @property
    def degrees(self) -> np.ndarray:
        return np.diff(self.indptr)

    def neighbors(self, i: int) -> np.ndarray:
        return self.indices[self.indptr[i] : self.indptr[i + 1]]

    @property
    def volume(self) -> int:
        """Total volume Σ deg = 2·|E| (≙ ``G.num_edges()`` as used in the
        conductance denominator)."""
        return int(self.indices.size)

    def adjacency(self, dtype=np.float64):
        """Dense (n, n) adjacency (for moderate graphs / ASE input)."""
        A = np.zeros((self.n, self.n), dtype=dtype)
        A[np.repeat(np.arange(self.n), self.degrees), self.indices] = 1.0
        return A

    def adjacency_bcoo(self, dtype=None):
        """Sparse BCOO adjacency."""
        dtype = dtype or jnp.asarray(0.0).dtype
        rows = np.repeat(np.arange(self.n), self.degrees)
        idx = np.stack([rows, self.indices], axis=1).astype(np.int32)
        data = np.ones(self.indices.size)
        return jsparse.BCOO(
            (jnp.asarray(data, dtype), jnp.asarray(idx)),
            shape=(self.n, self.n),
        )


def read_arc_list(path) -> SimpleGraph:
    """Build a :class:`SimpleGraph` from an arc list.

    Accepts anything ``io.open_source`` does: a local path, ``file://``
    or fsspec URL, raw bytes, or a ``ByteSource``.  For graphs too large
    to hold, use ``io.stream_arc_list`` and the streamed sketch path
    (``graph.stream``) instead.
    """
    from ..io.arclist import _chunk_lines, _parse_edge_block
    from ..io.source import open_source

    src = open_source(path)
    edges: list[tuple[str, str]] = []
    for block in _chunk_lines(src, 8 << 20):
        us, vs = _parse_edge_block(block)
        edges.extend(zip(us, vs))
    return SimpleGraph(edges)
