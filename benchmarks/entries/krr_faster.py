"""Entry kind ``krr_faster``: one whole exact-kernel ridge classification
a step by upstream's default algorithm (``skylark_krr -a 1``,
``ml.faster_kernel_rlsc``): the n x n Gaussian Gram matrix, the
random-feature preconditioner by Woodbury, preconditioned CG on
``(K + lam I) alpha = Y`` for the ten one-vs-all codes.

X (rows x d, f32) and the labels stay resident on the device.  They are
one problem from the configuration's ``data_seed``: ten class means, a
low-rank latent part and a little isotropic noise, the label of a row
the class of its mean.  ``--seed`` draws the order of the rows: K, the
features and the codes are permuted alike, so every seed is the same
system doing the same work and the iteration count is the
configuration's, not the seed's.  (Flipping the signs of X's columns
leaves K as it is too, but ``cos(X W' + b)`` of a mirrored X is that of
a mirrored W, another draw of the preconditioner: on the chip at the
cell's size 30 mirrored seeds read 28 to 32 iterations, 1.9 % of a step
each, and of three sets of six two spread over half of ``solve_s``'s
bound: PERF.md section 6, PR 31.)
The feature map's draws come from the configuration's fixed
``sketch_seed``: ``plans.apply`` keys its executable on the serialized
map, and a new one is a new program.

The plain reference is in this file and imports nothing of the program.
It reads the feature map's W and phase shifts as data from a map built
like the program's own (same seed, same order), centres X (K does not
change with a shift of every row; the squared distances lose less to
cancellation), builds K_ref = exp(-|x - y|^2 / 2 sigma^2) a row block at
a time, writes the Woodbury preconditioner out, and runs textbook
preconditioned CG to 1e-6, restarted once from the true residual.  All
of it is f32 at ``highest`` precision.  Compared, for every answer of
the window: ``resid_rel``, the residual of the stated system under a K
the program did not make, and ``pred_rel_err``, the predictions
``K_s alpha`` on sampled rows against the reference's.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = "highest"
REF_TOL = 1e-6     # the reference's own CG tolerance
REF_ITERS = 400    # and its iteration limit, a pass (it makes two)


def key_of(seed: int):
    """A PRNG key for any whole ``seed`` up to 64 bits (x64 is off)."""
    return jax.random.fold_in(jax.random.key(seed % 2**31), seed // 2**31 % 2**31)


def make_data(seed, data_seed, z):
    """X and the labels from ``data_seed``, the order of their rows from
    ``seed``.  A row is ``mean[c] + u B + noise e``: c one of ``targets``
    classes, ``mean`` rows of norm about ``class_scale``, u a standard
    normal ``latent_rank``-vector times ``latent_scale``, B's rows and e
    of norm about 1.  Made a row block at a time in one program."""
    rows, d, t, r = z["rows"], z["d"], z["targets"], z["latent_rank"]
    block = z["block"]

    @jax.jit
    def gen(data_key, key):
        km, kb, kx = jax.random.split(data_key, 3)
        means = z["class_scale"] / math.sqrt(d) * jax.random.normal(km, (t, d), F32)
        B = jax.random.normal(kb, (r, d), F32) / math.sqrt(d)

        def blk(k):
            kc, ku, ke = jax.random.split(k, 3)
            cls = jax.random.randint(kc, (block,), 0, t)
            u = z["latent_scale"] * jax.random.normal(ku, (block, r), F32)
            e = z["noise"] / math.sqrt(d) * jax.random.normal(ke, (block, d), F32)
            return means[cls] + jnp.matmul(u, B, precision=HI) + e, cls

        X, cls = jax.lax.map(blk, jax.random.split(kx, rows // block))
        order = jax.random.permutation(key, rows)
        return X.reshape(rows, d)[order], cls.reshape(rows)[order]

    return gen(key_of(data_seed), key_of(seed))


# -- the plain reference ----------------------------------------------------


def lower(x, dtype):
    """x as the control holds it: rounded to ``dtype``, computed in f32."""
    return x if dtype is None else x.astype(dtype).astype(F32)


def long_dot(A, B, block):
    """A B for A (p, n), B (n, q) with a long n: ``block`` terms at a
    time, the blocks' products added in f32.  The preconditioner below is
    a difference that has to be right to 5e-7 of its terms; one f32
    product over 49,152 terms on a v5e's MXU does not carry that, over a
    few thousand it does (PERF.md section 6, PR 31)."""
    p, n = A.shape
    parts = (A.reshape(p, n // block, block).transpose(1, 0, 2),
             B.reshape(n // block, block, B.shape[1]))

    def fold(acc, ab):
        return acc + jnp.matmul(ab[0], ab[1], precision=HI), None

    return jax.lax.scan(fold, jnp.zeros((p, B.shape[1]), F32), parts)[0]


@partial(jax.jit, static_argnames=("block", "dtype"))
def reference_gram(X, sigma, block, dtype=None):
    """K[i, j] = exp(-|x_i - x_j|^2 / (2 sigma^2)) of the centred rows, a
    row block at a time.  The control holds X and K in ``dtype``."""
    n, d = X.shape
    Xc = lower(X - jnp.mean(X, axis=0), dtype)
    sq = jnp.sum(Xc * Xc, axis=1)

    def blk(Xb):
        d2 = (jnp.sum(Xb * Xb, axis=1)[:, None] + sq[None, :]
              - 2.0 * jnp.matmul(Xb, Xc.T, precision=HI))
        return lower(jnp.exp(-jnp.maximum(d2, 0.0) / (2.0 * sigma * sigma)), dtype)

    return jax.lax.map(blk, Xc.reshape(n // block, block, d)).reshape(n, n)


@partial(jax.jit, static_argnames=("block", "dtype"))
def reference_precond(X, W, shifts, lam, block, dtype=None):
    """U~ = L^-1 Z' / lam, L = chol(I + Z'Z / lam), Z = sqrt(2/s)
    cos(X W' + shifts): with it (Z Z' + lam I)^-1 R = R / lam - U~'(U~ R)
    (Woodbury).  The triangular solve goes a block of Z's rows at a time
    (all 49,152 at once need 13 GB of temporaries on a v5e).  The control
    holds X, W, the phase and Z in ``dtype``; the s x s matrix and its
    factor stay f32 (rounded, it is not positive definite)."""
    n, s = X.shape[0], W.shape[0]
    phase = jnp.matmul(lower(X, dtype), lower(W, dtype).T, precision=HI)
    Z = lower(math.sqrt(2.0 / s) * jnp.cos(lower(phase + shifts, dtype)), dtype)
    C = jnp.eye(s, dtype=F32) + long_dot(Z.T, Z, block) / lam
    L = jnp.linalg.cholesky(C)
    U = jax.lax.map(
        lambda Zb: jax.scipy.linalg.solve_triangular(L, Zb.T, lower=True) / lam,
        Z.reshape(n // block, block, s))            # (blocks, s, block)
    return lower(U.transpose(1, 0, 2).reshape(s, n), dtype)


@partial(jax.jit, static_argnames=("block", "dtype"))
def reference_pcg(K, U, Y, A0, lam, block, dtype=None):
    """Preconditioned CG (Hestenes-Stiefel, preconditioner R / lam - U'(U R))
    on (K + lam I) A = Y from A0, every column until its residual is
    under ``REF_TOL`` of its right-hand side or ``REF_ITERS`` are done.
    The residual it starts from is the true one, so a second call is a
    restart.  The control rounds every product's operand to ``dtype``; a
    column whose P'(K + lam I)P is not positive (K rounded is not positive
    definite) stops where it is."""
    def op(P):
        return jnp.matmul(K, lower(P, dtype), precision=HI) + lam * P

    def precond(R):
        UR = long_dot(U, lower(R, dtype), block)
        return R / lam - jnp.matmul(U.T, lower(UR, dtype), precision=HI)

    bnorm = jnp.linalg.norm(Y, axis=0)

    def live(R, ok):
        return ok & (jnp.linalg.norm(R, axis=0) > REF_TOL * bnorm)

    def cond(c):
        return (c[0] < REF_ITERS) & jnp.any(live(c[2], c[5]))

    def body(c):
        it, A, R, P, rz, ok = c
        Q = op(P)
        pq = jnp.sum(P * Q, axis=0)
        ok = ok & (pq > 0)  # P'(K + lam I)P <= 0: not positive definite, stop there
        step = jnp.where(live(R, ok), rz / jnp.where(ok, pq, 1.0), 0.0)
        A, R = A + step * P, R - step * Q
        Zr = precond(R)
        rz_new = jnp.sum(R * Zr, axis=0)
        beta = rz_new / jnp.where(rz != 0, rz, 1.0)
        return it + 1, A, R, Zr + beta * P, rz_new, ok

    R0 = Y - op(A0)
    Z0 = precond(R0)
    start = (jnp.int32(0), A0, R0, Z0, jnp.sum(R0 * Z0, axis=0), bnorm >= 0)
    return jax.lax.while_loop(cond, body, start)[1]


@jax.jit
def sampled_predictions(K, alpha, idx):
    """The predictions K alpha on the rows ``idx``."""
    return jnp.matmul(K, alpha, precision=HI)[idx]


@jax.jit
def compare(K, Y, lam, idx, pred_ref, alpha):
    """(resid_rel, pred_rel_err) of one answer: the residual of the
    stated system, and its predictions on the rows ``idx`` against the
    reference's there."""
    Ka = jnp.matmul(K, alpha, precision=HI)
    return (jnp.linalg.norm(Y - Ka - lam * alpha) / jnp.linalg.norm(Y),
            jnp.linalg.norm(Ka[idx] - pred_ref) / jnp.linalg.norm(pred_ref))


COMPARED = ("resid_rel", "pred_rel_err")


# -- the cost functions (work the algorithm needs, from shapes) -------------


def gram_cost(sizes, info):
    """K from X: the cross term X X' is 2 n^2 d flop, and the n^2 f32
    entries are written once.  Compute-bound by the v5e's peaks; an f32
    product at ``highest`` is six bfloat16 passes, so a program that
    makes it so cannot read over 100 / 6 = 16.7 % of the bf16 peak."""
    n, d = sizes["rows"], sizes["d"]
    return 2.0 * n * n * d, 4.0 * (n * n + n * d)


def pcg_cost(sizes, info):
    """Preconditioned CG of ``info['cg_iters']`` iterations on t columns:
    an iteration multiplies by the symmetric K, of which no
    implementation can read less than the lower triangle, n (n + 1) / 2
    f32 entries, and by U~ (s x n) twice.  Memory-bound: a program that
    reads all of K shows at most about 57 %."""
    n, s, t, it = sizes["rows"], sizes["s"], sizes["targets"], info["cg_iters"]
    return (it * (2.0 * n * n * t + 4.0 * s * n * t),
            it * 4.0 * (n * (n + 1) / 2 + 2 * s * n))


COSTS = {"gram": gram_cost, "pcg": pcg_cost}


# -- the entry --------------------------------------------------------------


class Entry:
    def __init__(self, config, cell, seed, chips, tiny=False):
        self.sizes = {**config, **(config["rehearsal"] if tiny else {})}
        self.limits = cell["limits"]
        self.seed = seed
        self.timer = None  # the solver takes no phase timer

    def setup(self):
        self.X, self.Y = make_data(self.seed, self.sizes["data_seed"], self.sizes)
        jax.block_until_ready(self.Y)

    def step(self):
        from libskylark_tpu import SketchContext, ml

        z = self.sizes
        model = ml.faster_kernel_rlsc(
            ml.GaussianKernel(z["d"], z["sigma"]), self.X, self.Y, z["lam"], z["s"],
            SketchContext(seed=z["sketch_seed"]),
            ml.KrrParams(tolerance=z["tolerance"], iter_lim=z["iter_lim"]))
        alpha = jax.block_until_ready(model.A)
        bad = None if int(model.info["flag"]) == 0 else "CG did not converge"
        info = {"cg_iters": int(model.info["iterations"])}
        return {"answer": alpha, "units": {"solutions": 1}, "info": info, "bad": bad}

    def release(self):
        pass  # the call returns its coefficients; K and U~ died with it

    def draws(self):
        """The feature map's W (s x d, scaled by 1/sigma) and phase
        shifts, read as data from a map built like the program's own."""
        from libskylark_tpu import SketchContext, ml

        z = self.sizes
        rft = ml.GaussianKernel(z["d"], z["sigma"]).create_rft(
            z["s"], "regular", SketchContext(seed=z["sketch_seed"]))
        return rft._underlying.realize(F32), rft.shifts(F32)

    def codes(self):
        """The +-1 one-vs-all codes of the labels, (rows, targets)."""
        return jnp.where(self.Y[:, None] == jnp.arange(self.sizes["targets"]),
                         1.0, -1.0).astype(F32)

    def reference(self, dtype=None):
        """(K_ref, the codes, the reference's coefficients).  Each stage
        is waited for: a program's temporaries are held from the moment
        it is enqueued, and K leaves little room beside it."""
        z = self.sizes
        lam = jnp.float32(z["lam"])
        U = jax.block_until_ready(
            reference_precond(self.X, *self.draws(), lam, z["ref_block"], dtype))
        K = jax.block_until_ready(
            reference_gram(self.X, jnp.float32(z["sigma"]), z["ref_block"], dtype))
        Y = self.codes()
        alpha = reference_pcg(K, U, Y, jnp.zeros_like(Y), lam, z["ref_block"], dtype)
        return K, Y, jax.block_until_ready(
            reference_pcg(K, U, Y, alpha, lam, z["ref_block"], dtype))

    def check(self, answers):
        z = self.sizes
        K, Y, alpha_ref = self.reference()
        idx = jax.random.randint(key_of(self.seed + 1), (z["sample_rows"],), 0, z["rows"])
        pred_ref = sampled_predictions(K, alpha_ref, idx)
        lam = jnp.float32(z["lam"])

        def whole(alpha):
            # a model that holds fewer support rows has no weight on the rest
            return jnp.zeros_like(Y).at[: alpha.shape[0]].set(alpha)

        # one answer a call: a stack would be a new program for every count
        errs = [[float(v) for v in compare(K, Y, lam, idx, pred_ref, whole(a))]
                for a in answers]
        return [(name, max(e[i] for e in errs), self.limits[name])
                for i, name in enumerate(COMPARED)]

    def control(self):
        """The reference in the precision below the configuration's
        (bfloat16 for float32: X, K, the features and every product's
        operand), in the program's place."""
        return self.reference(jnp.bfloat16)[2]
