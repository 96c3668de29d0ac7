"""Entry kind ``graph_se``: one whole approximate adjacency spectral
embedding a step (upstream's ``skylark_graph_se``): ``graph.approximate_ase(
A, rank, SketchContext(sketch_seed), ASEParams(num_iterations=q,
sparse=True), return_info=True)`` of a resident sparse adjacency matrix.

Set-up goes the way ``cli/graph_se.py --sparse`` goes for an arc list of
integers: the arcs (two int32 arrays, here drawn on the device instead of
read) become the symmetric BCOO adjacency through
``graph.adjacency_from_edges`` and that the product's own layout through
``core.sparse.prepare``; both stay resident, and a step is the call
alone.  The answer that is kept is one ``(1 + n) x k`` array: the row of
eigenvalues, then the embedding X.

The graph is a degree-corrected planted partition (Karrer, Newman 2011)
drawn from the configuration's ``data_seed``: every arc picks a community
c by its share of the edges, stays inside it with probability 1 - mixing
(else its other end picks a community afresh), and picks each end inside
its community with probability proportional to a power law of the
vertex's rank there, ``(r + r0_c)^(-1/(tau - 1))``, which has a closed
inverse: no table is searched.  ``r0`` is solved so that the largest
expected degree over the mean is the configuration's.  The communities'
edge shares differ from their vertex shares (``sizes^share_power``,
smaller ones denser), which is what keeps the eight leading eigenvalues
apart.  ``--seed`` permutes the vertex labels and nothing else: every
seed is the same graph up to relabelling, and the same work.

The plain reference is in this file and imports nothing of the program.
It builds its own adjacency from the raw arcs (both directions, a two-key
sort, repeats and self-loops dropped, sorted by row) and runs the
recurrence of ``nla/svd.hpp:321-392`` in plain ``jax.numpy``, f32 at
``highest``: ``Y = A Omega'``; ``num_iterations`` times ``Y <- orth(A (A'
Y))``; ``T = Q'(A Q)`` symmetrized; ``eigh``; by |lambda| descending; the
first k; ``X = V sqrt|lambda|``.  Omega is read as data from a sketch
object built like the program's own.  A product is ``segment_sum(data *
Y[cols], rows)`` a block of edges at a time, each block summed into the
window of rows it spans (a segment sum over all n rows is a scatter the
chip takes 100 ns a nonzero for; into a window, 10); A' Y is the same
arrays, the sorted nonzeros of a symmetric matrix being those of its
transpose.  It orthonormalizes in its own way: Cholesky of the Gram matrix,
a shifted pass and then two (the program: eigh of the Gram matrix, twice).

Compared, for every answer of the window: ``lam_rel_err`` max_i |lambda_i
- lambda_i_ref| / |lambda_1_ref|; ``subspace_err`` |V V' - V_ref
V_ref'|_F / sqrt(2k), from the k x k cross-Gram matrices as the two
residuals |V - V_ref (V_ref' V)|_F and |V_ref - V (V' V_ref)|_F (the
difference 2k - 2 |V_ref' V|_F^2 cancels in f32); ``embed_rows_err``, the
relative Frobenius error of X_s X_s' on sampled vertices, blind to sign
and to rotation inside an eigenvalue cluster.  The control is the
reference with the panel, the gathered rows and the products in bfloat16.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32, I32 = jnp.float32, jnp.int32
HI = "highest"
SHIFT = 1e-3  # of the Gram's mean eigenvalue, in the first Cholesky pass
COMPARED = ("lam_rel_err", "subspace_err", "embed_rows_err")


def key_of(seed: int):
    """A PRNG key for any whole ``seed`` up to 64 bits (x64 is off)."""
    return jax.random.fold_in(jax.random.key(seed % 2**31), seed // 2**31 % 2**31)


# -- the graph ----------------------------------------------------------------


def rank_offset(n, tau, ratio):
    """r0 of the rank law ``w(r) = (r + r0)^-g``, g = 1/(tau - 1), over n
    ranks, such that the largest weight over the mean is ``ratio``."""
    g = 1.0 / (tau - 1.0)

    def got(r0):
        return r0 ** -g * n * (1 - g) / ((n + r0) ** (1 - g) - r0 ** (1 - g))

    lo, hi = 1e-6, float(n)
    for _ in range(200):
        mid = math.sqrt(lo * hi)
        lo, hi = (mid, hi) if got(mid) > ratio else (lo, mid)
    return lo


def communities(z):
    """(first vertex, vertices, edge share, r0) of every community."""
    n, shares = z["vertices"], np.asarray(z["community_sizes"], np.float64)
    sizes = np.floor(shares / shares.sum() * n).astype(np.int64)
    sizes[0] += n - sizes.sum()
    edge = (sizes / n) ** z["share_power"]
    edge = edge / edge.sum()
    # the densest community's largest expected degree is the configuration's
    dense = (edge / (sizes / n)).max()
    r0 = rank_offset(n, z["degree_exponent"],
                     z["max_degree"] / z["mean_degree"] / dense)
    return np.cumsum(sizes) - sizes, sizes, edge, r0 * sizes / n


def make_arcs(z, seed):
    """The arcs (u, v), int32, ``arcs_drawn`` of them, as the module
    docstring draws them: a block at a time in one program, the labels
    permuted by ``seed``."""
    first, sizes, edge, r0 = communities(z)
    e = 1.0 - 1.0 / (z["degree_exponent"] - 1.0)  # of the law's integral
    lo, hi = r0 ** e, (sizes + r0) ** e
    cum = jnp.asarray(np.cumsum(edge)[:-1], F32)
    first, last, r0, lo, hi = (jnp.asarray(a, t) for a, t in (
        (first, I32), (sizes - 1, I32), (r0, F32), (lo, F32), (hi, F32)))
    block, arcs = z["block"], z["arcs_drawn"]

    def community(x):
        return jnp.sum(x[:, None] >= cum[None, :], axis=1).astype(I32)

    def vertex(c, x):
        r = (lo[c] + x * (hi[c] - lo[c])) ** (1.0 / e) - r0[c]
        return first[c] + jnp.clip(r.astype(I32), 0, last[c])

    def draw(key):
        x = jax.random.uniform(key, (5, block), F32)
        c1 = community(x[0])
        c2 = jnp.where(x[1] < z["mixing"], community(x[2]), c1)
        return vertex(c1, x[3]), vertex(c2, x[4])

    @jax.jit
    def gen(key, perm):
        u, v = lax.map(draw, jax.random.split(key, -(-arcs // block)))
        return perm[u.reshape(-1)[:arcs]], perm[v.reshape(-1)[:arcs]]

    # the labels' permutation is drawn on the host: on the device it is a
    # sort, and a sort is a minute of the TPU compiler's time
    perm = np.random.default_rng(seed).permutation(z["vertices"]).astype(np.int32)
    return gen(key_of(z["data_seed"]), jnp.asarray(perm))


class Nonzeros:
    """The program's adjacency as the entry holds it: the BCOO's data and
    indices.  ``[:k]`` is the first k nonzeros (a planted fault leaves
    half of them out)."""

    def __init__(self, data, indices):
        self.data, self.indices = data, indices

    def __getitem__(self, cut):
        return Nonzeros(self.data[cut], self.indices[cut])


@jax.jit
def pack(lam, X):
    """The answer that is kept: [lambda; X]."""
    return jnp.concatenate([lam[None, :], X], axis=0)


# -- the plain reference ------------------------------------------------------


def lower(x, dtype):
    """x as the control holds it: rounded to ``dtype``, computed in f32."""
    return x if dtype is None else x.astype(dtype).astype(F32)


@jax.jit
def _sort_pairs(a, b):
    # (named and written as the program's own sort, so that the compile
    # cache holds one executable for both: a minute of a cold run)
    return lax.sort((a, b), num_keys=2)


@partial(jax.jit, static_argnames=("n",))
def first_copies(rows, cols, *, n):
    """Sorted pairs with every repeat moved to (n, n); how many are left."""
    same = jnp.concatenate([jnp.zeros((1,), bool),
                            (rows[1:] == rows[:-1]) & (cols[1:] == cols[:-1])])
    rows, cols = jnp.where(same, n, rows), jnp.where(same, n, cols)
    return rows, cols, jnp.sum(rows < n)


def adjacency(u, v, n, block):
    """(rows, cols, window): the nonzeros of the symmetric adjacency by
    row (every arc in both directions, self-loops and repeats dropped),
    padded to whole blocks of ``block`` with (n, n), and the most rows a
    block spans, as a power of two."""
    loop = u == v
    u, v = jnp.where(loop, n, u), jnp.where(loop, n, v)
    rows, cols = _sort_pairs(jnp.concatenate([u, v]), jnp.concatenate([v, u]))
    rows, cols, nnz = first_copies(rows, cols, n=n)
    rows, cols = _sort_pairs(rows, cols)
    size = -(-int(nnz) // block) * block
    rows, cols = (jnp.concatenate([a, jnp.full((block,), n, I32)])[:size]
                  for a in (rows, cols))
    by_block = rows.reshape(-1, block)
    span = int(jnp.max(jnp.minimum(by_block[:, -1], n - 1) - by_block[:, 0])) + 1
    return rows, cols, 1 << (span - 1).bit_length()


@partial(jax.jit, static_argnames=("block", "window", "dtype"))
def product(rows, cols, Y, *, block, window, dtype=None):
    """A Y for the unit nonzeros (rows, cols): a block of edges at a time,
    summed into the ``window`` rows from the block's first."""
    n, s = Y.shape
    Yp = jnp.concatenate([lower(Y, dtype), jnp.zeros((window + 1, s), F32)])

    def fold(out, rc):
        r, c = rc
        part = jax.ops.segment_sum(lower(Yp[c], dtype), r - r[0],
                                   num_segments=window, indices_are_sorted=True)
        at = jnp.minimum(r[0], n)
        return lax.dynamic_update_slice_in_dim(
            out, lax.dynamic_slice_in_dim(out, at, window) + part, at, axis=0), None

    out, _ = lax.scan(fold, jnp.zeros_like(Yp),
                      (rows.reshape(-1, block), cols.reshape(-1, block)))
    return lower(out[:n], dtype)


@jax.jit
def inverse_factor(G, shift):
    """R^-1 with R'R = G + shift * (mean eigenvalue of G) * I."""
    s = G.shape[0]
    eye = jnp.eye(s, dtype=F32)
    L = jnp.linalg.cholesky(G + shift * jnp.trace(G) / s * eye)
    return jax.scipy.linalg.solve_triangular(L, eye, lower=True).T


@jax.jit
def orth(Y):
    for shift in (SHIFT, 0.0, 0.0):
        Y = jnp.matmul(Y, inverse_factor(jnp.matmul(Y.T, Y, precision=HI), shift),
                       precision=HI)
    return Y


@partial(jax.jit, static_argnames=("k",))
def ritz(Q, AQ, *, k):
    T = jnp.matmul(Q.T, AQ, precision=HI)
    lam, W = jnp.linalg.eigh((T + T.T) / 2)
    order = jnp.argsort(-jnp.abs(lam))
    lam, W = lam[order], W[:, order]
    V = jnp.matmul(Q, W[:, :k], precision=HI)
    return pack(lam[:k], V * jnp.sqrt(jnp.abs(lam[:k]))[None, :]), lam


def reference_ase(u, v, n, omega, k, sweeps, block, dtype=None):
    """(the packed answer, every Ritz value) of the recurrence in the
    module docstring on the arcs (u, v); ``dtype`` (the control) holds the
    panel, the gathered rows and every product in a lower precision."""
    rows, cols, window = adjacency(u, v, n, block)
    A = partial(product, rows, cols, block=block, window=window, dtype=dtype)
    Q = orth(A(omega.T))
    for _ in range(sweeps):
        Q = orth(A(A(Q)))  # A' = A: the same sorted nonzeros
    return ritz(Q, A(Q), k=k)


@jax.jit
def compare(answer, ref, idx):
    """(lam_rel_err, subspace_err, embed_rows_err) of a packed answer."""
    def parts(a):
        lam, X = a[0], a[1:]
        return lam, X, X / jnp.sqrt(jnp.abs(lam))[None, :]

    def outside(V, W):  # |V - W (W'V)|_F^2
        return jnp.sum((V - jnp.matmul(W, jnp.matmul(W.T, V, precision=HI),
                                       precision=HI)) ** 2)

    def gram(X):
        return jnp.matmul(X[idx], X[idx].T, precision=HI)

    (lam, X, V), (lam_r, X_r, V_r) = parts(answer), parts(ref)
    k = lam.shape[0]
    return (jnp.max(jnp.abs(lam - lam_r)) / jnp.abs(lam_r[0]),
            jnp.sqrt((outside(V, V_r) + outside(V_r, V)) / (2 * k)),
            jnp.linalg.norm(gram(X) - gram(X_r)) / jnp.linalg.norm(gram(X_r)))


# -- the cost function --------------------------------------------------------


def ase_product_cost(sizes, info):
    """The products with A of one call (``info['products']``: the
    sketch's, two a sweep, the Ritz step's).  A product reads every
    nonzero's two indices and its value and one row of the panel for it,
    and writes the result: nnz (8 + 4 + 4 s) + n 4 s bytes, whatever
    layout holds the nonzeros (padding, a second copy of the indices and
    re-reads are the program's loss); its flop are a multiply and an add
    a nonzero and column.  Memory-bound by the v5e's peaks."""
    n, nnz, s = sizes["vertices"], sizes["nnz"], sizes["s"]
    return (info["products"] * 2.0 * nnz * s,
            info["products"] * (nnz * (12.0 + 4 * s) + n * 4.0 * s))


COSTS = {"ase_product_cost": ase_product_cost}


# -- the entry ----------------------------------------------------------------


class Entry:
    def __init__(self, config, cell, seed, chips, tiny=False):
        self.sizes = z = {**config, **(config["rehearsal"] if tiny else {})}
        z["s"] = min(z["rank"] * z["oversampling_ratio"] + z["oversampling_additive"],
                     z["vertices"])
        self.limits = cell["limits"]
        self.seed = seed
        self.timer = None  # the factorization takes no phase timer
        self._prepared = (None, None)

    def setup(self):
        from libskylark_tpu.graph import adjacency_from_edges

        z = self.sizes
        self.arcs = make_arcs(z, self.seed)
        A = adjacency_from_edges(*self.arcs, z["vertices"])
        self.A = Nonzeros(A.data, A.indices)
        # the nonzeros: what a planted fault halves, what the costs count
        z["rows"] = z["nnz"] = A.nse
        jax.block_until_ready(self.operand())

    def operand(self):
        """``self.A`` as the program takes it, in the product's layout:
        built once for the nonzeros as they stand."""
        from jax.experimental import sparse as jsparse

        from libskylark_tpu.core.sparse import prepare

        if self._prepared[0] is not self.A:
            n = self.sizes["vertices"]
            bcoo = jsparse.BCOO((self.A.data, self.A.indices), shape=(n, n),
                                indices_sorted=True, unique_indices=True)
            self._prepared = (self.A, prepare(bcoo, symmetric=True))
        return self._prepared[1]

    def step(self):
        from libskylark_tpu import SketchContext
        from libskylark_tpu.graph import ASEParams, approximate_ase

        z = self.sizes
        (X, lam), info = approximate_ase(
            self.operand(), z["rank"], SketchContext(seed=z["sketch_seed"]),
            ASEParams(oversampling_ratio=z["oversampling_ratio"],
                      oversampling_additive=z["oversampling_additive"],
                      num_iterations=z["num_iterations"], skip_qr=z["skip_qr"],
                      sparse=z["sparse"]),
            return_info=True)
        answer = jax.block_until_ready(pack(lam, X))
        return {"answer": answer, "units": {"solutions": 1}, "info": info, "bad": None}

    def release(self):
        self.A, self._prepared = None, (None, None)  # room for the reference

    def draws(self):
        """The sketch's Omega (s x n, scaled), read as data from a sketch
        object built like the program's own (same seed, same order)."""
        from libskylark_tpu import SketchContext
        from libskylark_tpu.sketch import JLT

        z = self.sizes
        return JLT(z["vertices"], z["s"],
                   SketchContext(seed=z["sketch_seed"])).realize(F32)

    def reference(self, dtype=None):
        z = self.sizes
        answer, ritz_values = reference_ase(
            *self.arcs, z["vertices"], self.draws(), z["rank"],
            z["num_iterations"], z["edge_block"], dtype)
        self.ritz_values = ritz_values
        return answer

    def check(self, answers):
        z = self.sizes
        if getattr(self, "_ref", None) is None:
            self._ref = jax.block_until_ready(self.reference())  # once a run
        idx = jax.random.randint(key_of(self.seed + 1), (z["sample_rows"],), 0,
                                 z["vertices"])
        # one answer a call: a stack would be a new program for every count
        errs = [[float(v) for v in compare(a, self._ref, idx)] for a in answers]
        return [(name, max(e[i] for e in errs), self.limits[name])
                for i, name in enumerate(COMPARED)]

    def control(self):
        """The reference in the precision below the configuration's
        (bfloat16 for float32), in the program's place."""
        return self.reference(jnp.bfloat16)
