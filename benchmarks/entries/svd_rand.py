"""Entry kind ``svd_rand``: one randomized rank-k SVD a step of a resident
operand whose rows are sharded over every chip of the cell.

The step is what ``cli/svd.py --shard`` does: ``shard_rows_padded(A,
default_mesh())`` once in set-up, then ``linalg.approximate_svd(A, k,
SketchContext(sketch_seed), SVDParams(num_iterations=q), return_info=True)``
with the guard on.  A is upstream's profile matrix (``nla/skylark_svd.cpp
--profile``): G1 (rows x k) G2 (k x d) + noise E, all standard normal, from
``--seed``; each chip makes its own rows, so the operand is never on one
device.  The answer that is kept is small: one array of 1 + d + sample_rows
rows and k columns, the row of sigma, then V, then U on a fixed sample of
rows (the same local rows on every chip's shard); U itself is dropped in
the step.  Every step rebuilds the sketch context from the configuration's
fixed ``sketch_seed`` (a new sketch seed is a new program, PERF.md
section 6).

The plain reference is in this file and imports nothing of the program:
Halko, Martinsson, Tropp's randomized subspace iteration in f32 at highest
precision, written out a chip at a time (``shard_map``: every tall product
is local, every small one a sum over the chips).  It
reads the sketch's Omega from a sketch object built like the program's own,
as data.  It orthonormalizes in its own way: the tall bases by Cholesky of
the Gram matrix (a shifted pass, then twice: a sketch of a noisy low-rank
matrix has a Gram matrix whose condition number passes 1/eps of f32, and
plain Cholesky breaks down there), the small d x s bases by Householder QR,
and after every product with A or A', where the program orthonormalizes
once a sweep.  The numbers compared do not depend on the basis chosen
inside a subspace: the singular values, the projector on V's span, and the
rank-k approximation U S V' on the sampled rows.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

F32 = jnp.float32
HI = "highest"
SHIFT = 1e-3  # of the Gram's mean eigenvalue, in the first Cholesky pass


def key_of(seed: int):
    """A PRNG key for any whole ``seed`` up to 64 bits (x64 is off)."""
    return jax.random.fold_in(jax.random.key(seed % 2**31), seed // 2**31 % 2**31)


def specs(mesh):
    """(rows over every axis of the mesh, replicated)."""
    return P(tuple(mesh.axis_names), None), P()


def make_operand(seed, mesh, rows, d, k, noise, block):
    """A = G1 G2 + noise E, row-sharded over ``mesh``: G2 (k x d) is drawn
    once, every chip draws G1 and E for its own rows a block at a time."""
    tall, small = specs(mesh)
    local = rows // mesh.size

    def shard(keys, G2):
        def blk(key):
            k1, ke = jax.random.split(key)
            G1 = jax.random.normal(k1, (block, k), F32)
            return (jnp.matmul(G1, G2, precision=HI)
                    + noise * jax.random.normal(ke, (block, d), F32))

        return jax.lax.map(blk, keys[0]).reshape(local, d)

    @jax.jit
    def gen(key):
        k2, kr = jax.random.split(key)
        keys = jax.random.split(kr, (mesh.size, local // block))
        G2 = jax.random.normal(k2, (k, d), F32)
        return jax.shard_map(shard, mesh=mesh, in_specs=(tall, small),
                             out_specs=tall)(keys, G2)

    return gen(key_of(seed))


@partial(jax.jit, static_argnames=("mesh",))
def take_rows(X, idx, mesh):
    """Row ``idx[c, j]`` of chip c's shard of X, for every c and j: a
    local gather on every chip, (idx.size, columns) in all."""
    tall, _ = specs(mesh)
    return jax.shard_map(lambda x, i: x[i[0]], mesh=mesh, in_specs=(tall, tall),
                         out_specs=tall)(X, idx)


@partial(jax.jit, static_argnames=("mesh",))
def pack(sv, V, U, idx, mesh):
    """The answer that is kept: [sigma; V; U on the sampled rows]."""
    return jnp.concatenate([sv[None, :], V, take_rows(U, idx, mesh)], axis=0)


# -- the plain reference ----------------------------------------------------


def lower(x, dtype):
    """x as the control holds it: rounded to ``dtype``, computed in f32."""
    return x if dtype is None else x.astype(dtype).astype(F32)


def products(mesh, dtype=None):
    """The two products of a row-sharded tall matrix, each chip on its own
    rows: ``tall_small(X, W) = X W`` (tall, local) and ``tall_tall(X, Y) =
    X' Y`` (small, summed over the chips).  The control rounds the operands
    to ``dtype`` and, where ``rounded``, the product.  A chip's rows go in
    one product: compiled for a v5e it holds no temporary, and cut into row
    blocks up to 6.3 GB of copies (PR 27)."""
    tall, small = specs(mesh)

    @jax.jit
    def tall_small(X, W):
        def local(X, W):
            return lower(jnp.matmul(lower(X, dtype), lower(W, dtype), precision=HI),
                         dtype)

        return jax.shard_map(local, mesh=mesh, in_specs=(tall, small),
                             out_specs=tall)(X, W)

    @partial(jax.jit, static_argnames=("rounded",))
    def tall_tall(X, Y, rounded=True):
        def local(X, Y):
            return jax.lax.psum(
                jnp.matmul(lower(X, dtype).T, lower(Y, dtype), precision=HI),
                mesh.axis_names)

        out = jax.shard_map(local, mesh=mesh, in_specs=(tall, tall),
                            out_specs=small)(X, Y)
        return lower(out, dtype) if rounded else out

    return tall_small, tall_tall


@jax.jit
def inverse_factor(G, shift):
    """R^-1 with R'R = G + shift * (mean eigenvalue of G) * I."""
    n = G.shape[0]
    eye = jnp.eye(n, dtype=F32)
    L = jnp.linalg.cholesky(G + shift * jnp.trace(G) / n * eye)
    return jax.scipy.linalg.solve_triangular(L, eye, lower=True).T


def reference_svd(A, omega, idx, k, sweeps, mesh, dtype=None):
    """The packed answer of a rank-k randomized SVD of A with the test
    matrix ``omega`` (s x d) and ``sweeps`` subspace iterations (Halko,
    Martinsson, Tropp 2011, algorithms 4.4 and 5.1).  ``dtype`` (the
    control) holds A, Omega, every tall product, the small bases and the
    projection A'Q in a lower precision; the Gram matrices and their
    Cholesky factors stay in f32 (a Gram matrix rounded to bfloat16 is
    not positive definite: the control would be NaN, not wrong)."""
    tall_small, tall_tall = products(mesh, dtype)

    def orth(Y):
        for shift in (SHIFT, 0.0, 0.0):
            Y = tall_small(Y, inverse_factor(tall_tall(Y, Y, rounded=False), shift))
        return Y

    Q = orth(tall_small(A, omega.T))
    for _ in range(sweeps):
        Z = jnp.linalg.qr(tall_tall(A, Q))[0]      # d x s, replicated
        Q = orth(tall_small(A, Z))
    W, sv, Zt = jnp.linalg.svd(tall_tall(A, Q), full_matrices=False)
    # A ~ Q (A'Q)' = (Q Zt') diag(sv) W': U on the sampled rows only
    Us = lower(jnp.matmul(take_rows(Q, idx, mesh), lower(Zt.T[:, :k], dtype),
                          precision=HI), dtype)
    return jnp.concatenate([sv[None, :k], lower(W[:, :k], dtype), Us], axis=0)


@partial(jax.jit, static_argnames=("d",))
def compare(answer, ref, d):
    """(sigma_rel_err, subspace_err, u_rows_err) of a packed answer."""
    def parts(a):
        return a[0], a[1:1 + d], a[1 + d:]

    def low_rank(sv, V, Us):
        return jnp.matmul(Us * sv, V.T, precision=HI)

    def projector(V):
        return jnp.matmul(V, V.T, precision=HI)

    (sv, V, Us), (sv_r, V_r, Us_r) = parts(answer), parts(ref)
    k = sv.shape[0]
    approx_r = low_rank(sv_r, V_r, Us_r)
    return (jnp.max(jnp.abs(sv - sv_r)) / sv_r[0],
            jnp.linalg.norm(projector(V) - projector(V_r)) / math.sqrt(2 * k),
            jnp.linalg.norm(low_rank(sv, V, Us) - approx_r) / jnp.linalg.norm(approx_r))


COMPARED = ("sigma_rel_err", "subspace_err", "u_rows_err")


# -- the cost function (one chip's share of the work, from shapes) ----------


def svd_power_cost(sizes, info):
    """The power sweeps on one chip of ``sizes['chips']``: a sweep reads
    the chip's rows of A twice in f32 (A'Y, then A (A'Y)) and cannot read
    them less; its flop are the two products (2 * 2 m d s) and the Gram
    orthonormalization's two passes of Y'Y and Y T (4 * 2 m s^2).  By the
    v5e's peaks the reads take longer: memory-bound.  The passes over Y
    are not counted, so the share is a floor."""
    m = sizes["rows"] / sizes["chips"]
    d, s, q = sizes["d"], sizes["s"], sizes["num_iterations"]
    return q * (4.0 * m * d * s + 8.0 * m * s * s), q * 2.0 * 4 * m * d


COSTS = {"svd_power": svd_power_cost}


# -- the entry --------------------------------------------------------------


class Entry:
    def __init__(self, config, cell, seed, chips, tiny=False):
        self.sizes = z = {**config, **(config["rehearsal"] if tiny else {})}
        z["s"] = min(z["k"] * z["oversampling_ratio"] + z["oversampling_additive"],
                     z["d"])
        # a rehearsal shards over the devices there are, the cell over its chips
        z["chips"] = min(chips, len(jax.devices())) if tiny else chips
        self.limits = cell["limits"]
        self.seed = seed
        self.timer = None  # the factorization takes no phase timer
        self._samples = {}  # sampled rows by row count (a planted fault halves it)

    def setup(self):
        from libskylark_tpu.parallel import default_mesh, shard_rows_padded

        z = self.sizes
        self.mesh = default_mesh(z["chips"])
        A = make_operand(self.seed, self.mesh, z["rows"], z["d"], z["k"],
                         z["noise"], z["block"])
        self.A, _ = shard_rows_padded(A, self.mesh)  # rows divide: no padding
        jax.block_until_ready(self.A)

    def sample(self, rows):
        """The sampled rows: the same ``sample_rows / chips`` local rows
        of every chip's shard, drawn once from the run's seed (and kept:
        the draw is no part of a step)."""
        if rows not in self._samples:
            z = self.sizes
            local = jax.random.randint(key_of(self.seed + 1),
                                       (z["sample_rows"] // z["chips"],), 0,
                                       rows // z["chips"])
            self._samples[rows] = jnp.tile(local, (z["chips"], 1))
        return self._samples[rows]

    def step(self):
        from libskylark_tpu import SketchContext
        from libskylark_tpu.linalg import SVDParams, approximate_svd

        z = self.sizes
        (U, sv, V), raw = approximate_svd(
            self.A, z["k"], SketchContext(seed=z["sketch_seed"]),
            SVDParams(oversampling_ratio=z["oversampling_ratio"],
                      oversampling_additive=z["oversampling_additive"],
                      num_iterations=z["num_iterations"], skip_qr=z["skip_qr"]),
            return_info=True)
        answer = pack(sv, V, U, self.sample(z["rows"]), self.mesh)
        del U
        jax.block_until_ready(answer)
        att, bad = raw["recovery"]["attempts"], None
        if len(att) != 1 or att[0]["verdict"] != "OK":
            bad = "recovery path: " + ", ".join(
                f"{a['action']}={a['verdict']}" for a in att)
        # a program from before the counter reports none: the metric is left out
        info = {"svd_attempts": raw["attempts"]} if "attempts" in raw else {}
        return {"answer": answer, "units": {"solutions": 1}, "info": info, "bad": bad}

    def release(self):
        pass  # a factorization leaves no state behind; A is the data

    def draws(self):
        """The sketch's Omega (s x d, scaled), read as data from a sketch
        object built like the program's own (same seed, same order)."""
        from libskylark_tpu import SketchContext
        from libskylark_tpu.sketch import JLT

        z = self.sizes
        return JLT(z["d"], z["s"], SketchContext(seed=z["sketch_seed"])).realize(F32)

    def reference(self, dtype=None):
        z = self.sizes
        return reference_svd(self.A, self.draws(), self.sample(z["rows"]), z["k"],
                             z["num_iterations"], self.mesh, dtype)

    def check(self, answers):
        # one answer a call: a stack would be a new program for every count
        ref = self.reference()
        errs = [[float(v) for v in compare(a, ref, self.sizes["d"])] for a in answers]
        return [(name, max(e[i] for e in errs), self.limits[name])
                for i, name in enumerate(COMPARED)]

    def control(self):
        """The reference in the precision below the configuration's
        (bfloat16 for float32), in the program's place."""
        return self.reference(jnp.bfloat16)
