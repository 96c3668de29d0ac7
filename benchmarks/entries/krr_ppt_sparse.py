"""Entry kind ``krr_ppt_sparse``: one whole streamed polynomial-kernel
ridge training call a step on a sparse X (``ml.streaming_kernel_ridge``
with ``ml.PolynomialKernel``: TensorSketch features, ``sketch/ppt.py``,
each level's CountSketch of a sparse panel into dense bins).

X is made on the host as a user holds it after reading a sparse LIBSVM
file: (row, column) int32 pairs sorted by row and bf16 values.  In
set-up the program lays them out in row panels on the device
(``core.sparse.panels``: every panel one count of slots, K a row in
place and the rest of the longer rows in overflow rows), and the trainer
walks them through ``core.sparse.panel_rows`` and ``block_args``; the
triplets stay on the host for the reference.  The
feature map is one chunk (``max_split = 2 s``): five panel passes a call,
``info["feature_passes"]``, and ``info["feature_nnz"]`` nonzeros fed to
the map.  The map's draws come from the configuration's fixed
``sketch_seed``.

The plain reference is in this file and imports nothing of the program:
it reads the map's draws from the trained model as data (each level's
CountSketch buckets and signs, the constant's bucket and sign), and for
a block of rows of the triplets scatters each nonzero into (rows, S) bins at
its column's bucket, times its sign (``zeros.at[row, bucket[col]].add``),
takes the levels' complex64 FFTs, their product and the inverse, sums
Z'Z and Z'Y over the blocks in f32 at highest precision and solves by
Cholesky; it compares predictions on a sample of the training rows drawn
from the seed, with its own features under the program's coefficients.
"""

from __future__ import annotations

import math
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = "highest"
CHUNK = 65536  # rows the host makes at a time


def key_of(seed: int):
    """A PRNG key for any whole ``seed`` up to 64 bits (x64 is off)."""
    return jax.random.fold_in(jax.random.key(seed % 2**31), seed // 2**31 % 2**31)


def row_lengths(rng, rows, nnz, real_cols, sigma):
    """Nonzeros of every row: the ``real_cols`` real-valued columns, one
    token, and tokens in proportion to a seeded log-normal draw of shape
    ``sigma`` (right-skewed, as a URL's tokens go with its length),
    rounded down, with one more on as many rows (drawn) as make the rows
    add up to ``nnz`` exactly."""
    t = rng.lognormal(0.0, sigma, rows)
    left = nnz - rows * (real_cols + 1)
    length = real_cols + 1 + np.floor(t * (left / t.sum())).astype(np.int64)
    length[rng.choice(rows, nnz - int(length.sum()), replace=False)] += 1
    return length


def make_data(seed, rows, d, nnz, targets, real_cols, sigma, dtype):
    """The sparse X (rows x d, ``nnz`` nonzeros) on the host as a user
    holds it after reading a LIBSVM file: ``data`` (nnz,) in ``dtype`` and
    ``indices`` (nnz, 2) int32 (row, column), sorted by row, columns
    ascending within a row; and the +-1 one-vs-all codes Y of a seeded
    sparse linear teacher's classes, on the device.  Made in chunks of
    rows (each from its own seeded stream, in threads).  Every row holds
    the ``real_cols`` real-valued columns (values in (0, 1]) and then its
    tokens, value 1: Zipf(1) column ids over the rest, distinct within
    the row (sorted log-uniform draws made strictly increasing)."""
    from concurrent.futures import ThreadPoolExecutor

    rng = np.random.default_rng(seed)
    length = row_lengths(rng, rows, nnz, real_cols, sigma)
    most = int(length.max()) - real_cols
    span = d - real_cols - most  # Zipf draws; the shift to distinct ids adds < most
    big = np.int64(span + most + 1)  # keeps each row's running maximum its own
    teacher = rng.standard_normal((d, targets), dtype=np.float32)
    starts = np.concatenate([[0], np.cumsum(length)])
    idx = np.empty((nnz, 2), np.int32)
    val = np.empty((nnz,), dtype)
    Y = np.empty((rows, targets), np.float32)
    log_span = np.log(span)

    def chunk(c):
        r0 = c * CHUNK
        L = length[r0:r0 + CHUNK]
        n = L.shape[0]
        T = L - real_cols
        crng = np.random.default_rng([seed, c])
        # sorted uniforms a row: cumulative exponential spacings over T + 1
        ends = np.cumsum(T + 1)
        cs = np.cumsum(crng.standard_exponential(int(ends[-1])), dtype=np.float64)
        base = np.concatenate([[0.0], cs[ends[:-1] - 1]])
        token = np.ones(cs.size, bool)
        token[ends - 1] = False
        u = (cs[token] - np.repeat(base, T)) / np.repeat(cs[ends - 1] - base, T)
        z = np.minimum(np.floor(np.exp(u * log_span)) - 1, span - 1).astype(np.int64)
        k = np.arange(z.size) - np.repeat(np.cumsum(T) - T, T)
        own = np.repeat(np.arange(n, dtype=np.int64), T) * big
        z = k + np.maximum.accumulate(z - k + own) - own
        at, to = starts[r0], starts[r0 + n]
        first = np.cumsum(L) - L  # a row's first nonzero, in the chunk
        real = (first[:, None] + np.arange(real_cols)).ravel()
        tok = np.repeat(first + real_cols, T) + k
        cols = np.empty(to - at, np.int32)
        vals = np.ones(to - at, np.float32)
        cols[real] = np.tile(np.arange(real_cols, dtype=np.int32), n)
        cols[tok] = real_cols + z
        vals[real] = 1.0 - crng.random(real.size, dtype=np.float32)
        vals = vals.astype(dtype)
        score = np.add.reduceat(vals.astype(np.float32)[:, None] * teacher[cols], first, axis=0)
        Y[r0:r0 + n] = np.where(np.argmax(score, 1)[:, None] == np.arange(targets), 1.0, -1.0)
        idx[at:to, 0] = np.repeat(np.arange(r0, r0 + n, dtype=np.int32), L)
        idx[at:to, 1] = cols
        val[at:to] = vals

    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        for done in pool.map(chunk, range(-(-rows // CHUNK))):
            del done
    return val, idx, jnp.asarray(Y)


# -- the plain reference ----------------------------------------------------


def lower(x, dtype):
    """x as the control holds it: rounded to ``dtype``, computed in f32;
    a complex x part by part."""
    if dtype is None:
        return x
    if jnp.iscomplexobj(x):
        return jax.lax.complex(lower(x.real, dtype), lower(x.imag, dtype))
    return x.astype(dtype).astype(F32)


def dft_tables(s, dtype):
    """(cos, sin) of 2 pi jk / s, (s, s) f32 rounded to ``dtype``: the
    control's transforms."""
    j = jnp.arange(s, dtype=jnp.int32)
    theta = (2.0 * math.pi / s) * ((j[:, None] * j[None, :]) % s).astype(F32)
    return lower(jnp.cos(theta), dtype), lower(jnp.sin(theta), dtype)


def features(row, col, x, nrows, B, Sg, idx, val, gamma, c, s, dtype=None):
    """TensorSketch features (nrows, s) of the rows whose nonzeros are
    (``row`` local to the block, ``col``, ``x``; a slot with x = 0 adds
    nothing): for each level the CountSketch of sqrt(gamma) x, scattered
    into bins at its column's bucket times its sign, plus sqrt(c) s_l at
    bucket h_l, its complex FFT, the levels' product, the inverse FFT's
    real part.  The control holds in ``dtype`` the values, each level's
    sketch, the transforms' tables (forward W (C - i S), inverse
    Re(P (C + i S)) / s), each transform's output, the product and the
    features."""
    tables = None if dtype is None else dft_tables(s, dtype)
    x = lower(x.astype(F32), dtype)
    P = None
    for l in range(B.shape[0]):
        W = jnp.zeros((nrows, s), F32).at[row, B[l][col]].add(Sg[l][col] * x)
        W = lower((math.sqrt(gamma) * W).at[:, idx[l]].add(math.sqrt(c) * val[l]), dtype)
        if tables is None:
            F = jnp.fft.fft(W.astype(jnp.complex64), axis=1)
        else:
            F = jax.lax.complex(lower(jnp.matmul(W, tables[0], precision=HI), dtype),
                                lower(-jnp.matmul(W, tables[1], precision=HI), dtype))
        P = F if P is None else lower(P * F, dtype)
    if tables is None:
        return jnp.real(jnp.fft.ifft(P, axis=1))
    Z = (jnp.matmul(P.real, tables[0], precision=HI)
         - jnp.matmul(P.imag, tables[1], precision=HI)) / s
    return lower(Z, dtype)


@partial(jax.jit, static_argnames=("block", "gamma", "c", "s", "dtype"))
def _fold(G, rhs, row, col, x, Yb, B, Sg, idx, val, block, gamma, c, s, dtype):
    """G + Z'Z and rhs + Z'Y of one block's features."""
    Z = features(row, col, x, block, B, Sg, idx, val, gamma, c, s, dtype)
    return (G + jnp.matmul(Z.T, Z, precision=HI), rhs + jnp.matmul(Z.T, Yb, precision=HI))


def reference_ridge(data, indices, Y, B, Sg, idx, val, gamma, c, lam, s, block,
                    dtype=None):
    """(Z'Z + lam I) C = Z'Y over blocks of ``block`` rows of the host
    triplets (``data``, ``indices``, sorted by row), solved by Cholesky.
    A block's nonzeros are one stretch of the triplets, put on the device
    a block at a time and padded to the largest block's count (value 0)."""
    n, t = Y.shape
    edges = np.searchsorted(indices[:, 0], np.arange(0, n + 1, block))
    window = int(np.diff(edges).max())
    G, rhs = lam * jnp.eye(s, dtype=F32), jnp.zeros((s, t), F32)
    for b in range(n // block):
        at, to = int(edges[b]), int(edges[b + 1])
        row, col = np.zeros(window, np.int32), np.zeros(window, np.int32)
        x = np.zeros(window, data.dtype)
        row[:to - at] = indices[at:to, 0] - b * block
        col[:to - at], x[:to - at] = indices[at:to, 1], data[at:to]
        G, rhs = _fold(G, rhs, row, col, x, Y[b * block:(b + 1) * block], B, Sg, idx, val,
                       block, gamma, c, s, dtype)
    return jax.scipy.linalg.cho_solve(jax.scipy.linalg.cho_factor(G, lower=True), rhs)


def sample_features(key, data, indices, B, Sg, idx, val, k, gamma, c, s):
    """The reference's features of k training rows drawn from ``key``
    (rows of the host triplets, put on the device padded to the longest
    drawn row)."""
    row = indices[:, 0]
    pick = np.asarray(jax.random.randint(key, (k,), 0, int(row[-1]) + 1))
    first = np.searchsorted(row, pick)
    count = np.searchsorted(row, pick, side="right") - first
    live = np.arange(int(count.max()))[None, :] < count[:, None]
    at = np.where(live, first[:, None] + np.arange(live.shape[1]), 0)
    local = np.broadcast_to(np.arange(k, dtype=np.int32)[:, None], at.shape)
    x = np.where(live, data[at], np.zeros((), data.dtype))
    return _sample_features(local.ravel(), indices[at, 1].ravel(), x.ravel(),
                            B, Sg, idx, val, k, gamma, c, s)


@partial(jax.jit, static_argnames=("k", "gamma", "c", "s"))
def _sample_features(row, col, x, B, Sg, idx, val, k, gamma, c, s):
    return features(row, col, x, k, B, Sg, idx, val, gamma, c, s)


@jax.jit
def prediction_err(Zs, C, C_ref):
    """||Zs C - Zs C_ref||_F / ||Zs C_ref||_F."""
    ref = jnp.matmul(Zs, C_ref, precision=HI)
    return jnp.linalg.norm(jnp.matmul(Zs, C, precision=HI) - ref) / jnp.linalg.norm(ref)


# -- the cost functions -----------------------------------------------------


def ppt_scatter_cost(sizes, info):
    """Flop and bytes the sparse CountSketches of a call need, whatever
    implements them: for every nonzero fed to the map
    (``info["feature_nnz"]``: nonzeros x levels x passes) a multiply and
    an add, its triplet read as held (10 bytes: a bf16 value, int32 row
    and column) and its bucket and sign (8 bytes); and each level's
    (rows, S) f32 bins written once a pass.  Memory-bound."""
    nz, passes = info["feature_nnz"], info["feature_passes"]
    bins = sizes["q"] * passes * sizes["rows"] * sizes["s"] * 4.0
    return 2.0 * nz, nz * (10.0 + 8.0) + bins


def ppt_features_cost(sizes, info):
    """Flop and bytes the TensorSketch features of a call need, counted
    as ``krr_ppt``'s are with the hash on the nonzeros: a pass, 2 q nnz
    for the CountSketches and, a row, q forward transforms and one
    inverse as real-input FFTs (2.5 S log2 S each) and the q - 1 level
    products of S/2 + 1 complex numbers (6 flop each); the triplets read
    once (10 bytes a nonzero).  A floor: the program's dense DFT does
    far more."""
    n, nnz, s, q = sizes["rows"], sizes["nnz"], sizes["s"], sizes["q"]
    row = (q + 1) * 2.5 * s * math.log2(s) + 6.0 * (q - 1) * (s // 2 + 1)
    passes = info["feature_passes"]
    return passes * (n * row + 2.0 * q * nnz), passes * nnz * 10.0


COSTS = {"ppt_scatter": ppt_scatter_cost, "ppt_features": ppt_features_cost}


# -- the entry --------------------------------------------------------------


class Entry:
    def __init__(self, config, cell, seed, chips, tiny=False):
        self.sizes = {**config, **(config["rehearsal"] if tiny else {})}
        self.limits = cell["limits"]
        self.seed = seed
        self.timer = None  # a PhaseTimer in the traced run only
        self.C_ref = None

    def kernel(self):
        from libskylark_tpu import ml

        z = self.sizes
        return ml.PolynomialKernel(z["d"], q=z["q"], c=z["c"], gamma=z["gamma"])

    def setup(self):
        from libskylark_tpu.core import sparse

        # A program without the row panels cannot run the cell: refused
        # here, before the data are made.
        if not hasattr(sparse, "panels"):
            raise RuntimeError("this program has no core.sparse.panels: the cell "
                               "needs the sparse TensorSketch route")
        z = self.sizes
        self.dtype = jnp.dtype(z["feature_dtype"])
        # the triplets stay on the host: the reference's input, read
        # after the window; the device holds the panels
        self.data, self.indices, self.Y = make_data(
            self.seed, z["rows"], z["d"], z["nnz"], z["targets"], z["real_cols"],
            z["row_sigma"], self.dtype)
        self.panels = sparse.panels((self.data, self.indices), z["block_rows"],
                                    shape=(z["rows"], z["d"]))
        jax.block_until_ready((self.panels, self.Y))

    def step(self):
        from libskylark_tpu import SketchContext, ml
        from libskylark_tpu.core import sparse

        z = self.sizes
        model = ml.streaming_kernel_ridge(
            self.kernel(), sparse.panel_rows, (z["rows"], z["d"]), self.Y, z["lam"],
            z["s"], SketchContext(seed=z["sketch_seed"]),
            ml.KrrParams(max_split=2 * z["s"], iter_lim=z["sweeps"]),
            block_rows=z["block_rows"], feature_dtype=self.dtype,
            block_args=(self.panels,), timer=self.timer,
        )
        jax.block_until_ready(model.W)
        self.model = model
        info = dict(model.info)
        bad = None if info["feature_passes"] == 1 + 2 * z["sweeps"] else (
            f"{info['feature_passes']} feature passes, not {1 + 2 * z['sweeps']}")
        return {"answer": model.W, "units": {"rows": z["rows"] * z["sweeps"]},
                "info": info, "bad": bad}

    def release(self):
        """Keep the map's draws (data) and the host triplets, drop the
        trained model and the program's panels."""
        M, z = self.model.maps[0], self.sizes
        assert (M.q, M.c, M.gamma) == (z["q"], z["c"], z["gamma"])
        self.B = jnp.stack([w.buckets() for w in M._cwts])
        self.Sg = jnp.stack([w.values(F32) for w in M._cwts])
        self.idx, self.val = M._hash_consts(F32)
        del self.model, self.panels

    def reference(self, levels=None, dtype=None):
        """The reference's coefficients; ``levels`` keeps the first so
        many levels (a planted fault), ``dtype`` is the control's."""
        z = self.sizes
        q = z["q"] if levels is None else levels
        return reference_ridge(self.data, self.indices, self.Y, self.B[:q], self.Sg[:q],
                               self.idx, self.val, z["gamma"], z["c"], z["lam"], z["s"],
                               z["ref_block"], dtype)

    def check(self, answers):
        z = self.sizes
        if self.C_ref is None:
            self.C_ref = self.reference()
        Zs = sample_features(key_of(self.seed + 1), self.data, self.indices, self.B, self.Sg,
                             self.idx, self.val, z["sample_rows"], z["gamma"], z["c"], z["s"])
        # one answer a call: a stack would be a new program for every count
        err = max(float(prediction_err(Zs, C, self.C_ref)) for C in answers)
        return [("pred_rel_err", err, self.limits["pred_rel_err"])]

    def control(self):
        """The reference in the precision below the configuration's
        (fp8 e4m3 for bfloat16 features), in the program's place."""
        return self.reference(dtype=jnp.float8_e4m3fn)
