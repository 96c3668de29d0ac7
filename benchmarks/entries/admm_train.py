"""Entry kind ``admm_train``: one whole BlockADMM training call a step
(``ml.BlockADMMSolver(loss, regularizer, maps, ADMMParams(...)).train``,
upstream's ``skylark_ml``): block-splitting consensus ADMM for
``sum_i loss(o_i, y_i) + lam reg(W)`` over J blocks of random Fourier
features, one row partition on this chip, the feature blocks remade
every iteration (upstream's default: its transform cache is off).

X (rows x d, bfloat16) and the labels stay resident on the device; the
maps are built once in set-up.  The data are the KRR configuration's: X
standard normal from ``--seed``, the labels the classes of a seeded
linear teacher.  The maps' draws come from the configuration's fixed
``sketch_seed``: the trainer's programs are keyed by the serialized maps
and a new seed is a new program.

The plain reference is in this file and imports nothing of the program.
It reads each map's W and phase shifts as data and runs the recurrence of
``BlockADMM.hpp:374-590`` for one partition, written out in plain
``jax.numpy``, f32 at ``highest`` precision, from zero for the same
number of iterations: the hinge (or squared) loss's prox and the l2 prox
from their definitions, every block's features made a row block at a
time, twice an iteration, ``Z_j Z_j' + I`` factored by Cholesky.  With
P = 1 the consensus is ``(Wi + W) / 2``.  The objective of an iteration
is the trainer's: the loss at the consensus the iteration started from
plus ``lam reg`` of the one it ends with.

Compared, for every answer of the window (an answer is the consensus
coefficients and the objective trace in one vector): ``pred_rel_err``,
the predictions on sampled rows with the reference's own features, and
``obj_rel_err``, the largest relative distance of the objective trace
from the reference's, which holds the program to every iteration and not
only the last.  ``label_agree`` is printed, not limited.
"""

from __future__ import annotations

import math
import sys
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = "highest"
COMPARED = ("pred_rel_err", "obj_rel_err")


def key_of(seed: int):
    """A PRNG key for any whole ``seed`` up to 64 bits (x64 is off)."""
    return jax.random.fold_in(jax.random.key(seed % 2**31), seed // 2**31 % 2**31)


def make_data(seed, rows, d, targets, block, dtype):
    """X ~ N(0, 1) in ``dtype`` and the classes (0 .. targets-1, int32)
    of a seeded linear teacher, a row block at a time in one program."""

    @jax.jit
    def gen(key):
        kx, kt = jax.random.split(key)
        T = jax.random.normal(kt, (d, targets), F32)

        def blk(k):
            X = jax.random.normal(k, (block, d), F32).astype(dtype)
            return X, jnp.argmax(jnp.matmul(X.astype(F32), T, precision=HI), axis=1)

        X, cls = jax.lax.map(blk, jax.random.split(kx, rows // block))
        return X.reshape(rows, d), cls.reshape(rows).astype(jnp.int32)

    return gen(key_of(seed))


# -- the plain reference ----------------------------------------------------


def lower(x, dtype):
    """x as a control holds it: rounded to ``dtype``, computed in f32."""
    return x if dtype is None else x.astype(dtype).astype(F32)


def features(X, W, shifts, dtype=None):
    """sqrt(2/s) cos(X W' + shifts), f32 at highest precision.  The fp8
    control holds what a feature pipeline in ``dtype`` holds in it: X,
    W, the product, the phase and the features (the sums stay f32)."""
    s = W.shape[0]
    WX = jnp.matmul(lower(X.astype(F32), dtype), lower(W, dtype).T, precision=HI)
    phase = lower(lower(WX, dtype) + lower(shifts, dtype), dtype)
    return lower(math.sqrt(2.0 / s) * jnp.cos(phase), dtype)


def codes(y, k):
    """+1 in the row of an example's class, -1 elsewhere: (k, n)."""
    return jnp.where(y[None, :] == jnp.arange(k)[:, None], 1.0, -1.0).astype(F32)


def loss_value(loss, O, C):
    if loss == "hinge":     # sum max(0, 1 - c o)
        return jnp.sum(jnp.maximum(0.0, 1.0 - C * O))
    return 0.5 * jnp.sum((O - C) ** 2)  # squared


def loss_prox(loss, V, t, C):
    """argmin_x t loss(x, c) + (x - v)^2 / 2, elementwise."""
    if loss == "hinge":
        cv = C * V
        return jnp.where(cv > 1.0, V, jnp.where(cv < 1.0 - t, V + t * C, C))
    return (V + t * C) / (1.0 + t)


def blocked(a, nb):
    """(k, n) -> (nb, k, n / nb): row blocks on the leading axis."""
    k, n = a.shape
    return a.reshape(k, nb, n // nb).transpose(1, 0, 2)


def unblocked(a):
    nb, k, b = a.shape
    return a.transpose(1, 0, 2).reshape(k, nb * b)


@partial(jax.jit, static_argnames=("block", "fdtype"))
def reference_factors(X, Ws, shifts, block, fdtype=None):
    """chol(Z_j Z_j' + I) of every block, Z_j made a row block at a time."""
    n, d = X.shape
    X3 = X.reshape(n // block, block, d)

    def one(W, sh):
        def fold(G, Xb):
            Z = features(Xb, W, sh, fdtype)
            return G + jnp.matmul(Z.T, Z, precision=HI), None

        s = W.shape[0]
        G = jax.lax.scan(fold, jnp.eye(s, dtype=F32), X3)[0]
        return jnp.linalg.cholesky(G)

    return [one(W, sh) for W, sh in zip(Ws, shifts)]


@partial(jax.jit, static_argnames=("loss", "block", "sdtype", "fdtype"))
def reference_iteration(state, X, y, Ws, shifts, Ls, rho, lam, *, loss, block,
                        sdtype=None, fdtype=None):
    """One iteration of the recurrence for one partition.  ``sdtype``
    (the bf16 control) rounds the state, the right-hand sides and both
    operands and the result of every thin product; ``fdtype`` (the fp8
    control) the feature pipeline."""
    rnd = partial(lower, dtype=sdtype)
    Wbar, W, mu, O, Obar, nu, del_o, mu_ij, ZtObar = state
    n, d = X.shape
    k, J = Wbar.shape[1], len(Ws)
    nb = n // block
    X3 = X.reshape(nb, block, d)
    C = codes(y, k)

    mu_ij = rnd(mu_ij - Wbar)
    Obar = rnd(Obar - nu)
    O = rnd(loss_prox(loss, Obar, 1.0 / rho, C))
    W = rnd((Wbar - mu) / (1.0 + lam / rho))      # prox of lam/rho |.|^2 / 2
    dsum = rnd(del_o / (J + 1.0) + nu)
    d3 = blocked(dsum, nb)

    def thin(a, b):
        return rnd(jnp.matmul(rnd(a), rnd(b), precision=HI))

    sum_o = jnp.zeros_like(O)
    wbar_out = jnp.zeros_like(O)
    Wi, zto, lo = [], [], 0
    for Wj, sh, L in zip(Ws, shifts, Ls):
        hi = lo + Wj.shape[0]
        Wb = Wbar[lo:hi]

        def first(acc, xs):
            Xb, db = xs
            Z = features(Xb, Wj, sh, fdtype)                # (block, s_j)
            return acc + thin(Z.T, db.T), thin(Wb.T, Z.T)   # (s_j, k), (k, block)

        acc, wo = jax.lax.scan(first, jnp.zeros_like(Wb), (X3, d3))
        rhs = rnd(Wb - mu_ij[lo:hi] + ZtObar[lo:hi] + rnd(acc))
        Wij = rnd(jax.scipy.linalg.cho_solve((L, True), rhs))

        def second(acc, Xb):
            Z = features(Xb, Wj, sh, fdtype)
            o = thin(Wij.T, Z.T)                            # (k, block)
            return acc + thin(Z.T, o.T), o

        z, o = jax.lax.scan(second, jnp.zeros_like(Wb), X3)
        Wi.append(Wij)
        zto.append(rnd(z))
        sum_o = sum_o + unblocked(o)
        wbar_out = wbar_out + unblocked(wo)
        lo = hi
    Wi = jnp.concatenate(Wi)
    mu_ij = rnd(mu_ij + Wi)
    ZtObar = jnp.concatenate(zto)

    del_o = rnd(O - rnd(sum_o))
    Obar = rnd(O - del_o / (J + 1.0))
    nu = rnd(nu + O - Obar)
    Wbar = rnd((Wi + W) / 2.0)                    # one partition: P + 1 = 2
    mu = rnd(mu + W - Wbar)
    obj = loss_value(loss, wbar_out, C) + lam * 0.5 * jnp.sum(Wbar * Wbar)
    return (Wbar, W, mu, O, Obar, nu, del_o, mu_ij, ZtObar), obj


def reference_train(X, y, Ws, shifts, z, sdtype=None, fdtype=None):
    """The recurrence from zero for ``maxiter`` iterations: the answer
    vector (consensus coefficients, then the objective trace)."""
    n, k, D = X.shape[0], z["targets"], sum(W.shape[0] for W in Ws)
    Ls = jax.block_until_ready(reference_factors(X, Ws, shifts, z["ref_block"], fdtype))
    small, tall = jnp.zeros((D, k), F32), jnp.zeros((k, n), F32)
    state = (small, small, small, tall, tall, tall, tall, small, small)
    objs = []
    for _ in range(z["maxiter"]):
        state, obj = reference_iteration(
            state, X, y, Ws, shifts, Ls, jnp.float32(z["rho"]), jnp.float32(z["lam"]),
            loss=z["loss"], block=z["ref_block"], sdtype=sdtype, fdtype=fdtype)
        objs.append(obj)
    return pack(state[0], jnp.stack(objs))


def pack(Wbar, objs):
    return jnp.concatenate([Wbar.astype(F32).reshape(-1), jnp.asarray(objs, F32)])


def unpack(answer, D, k):
    return answer[: D * k].reshape(D, k), answer[D * k:]


@partial(jax.jit, static_argnames=("rows",))
def sample_features(key, X, Ws, shifts, rows):
    """The reference's features, all blocks, of ``rows`` training rows
    drawn from ``key``."""
    Xs = X[jax.random.randint(key, (rows,), 0, X.shape[0])]
    return jnp.concatenate([features(Xs, W, sh) for W, sh in zip(Ws, shifts)], axis=1)


@jax.jit
def compare(Zs, Wbar, objs, Wbar_ref, objs_ref):
    """(pred_rel_err, obj_rel_err, label_agree) of one answer."""
    ref = jnp.matmul(Zs, Wbar_ref, precision=HI)
    got = jnp.matmul(Zs, Wbar, precision=HI)
    return (jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref),
            jnp.max(jnp.abs(objs - objs_ref) / objs_ref),
            jnp.mean(jnp.argmax(got, axis=1) == jnp.argmax(ref, axis=1)))


# -- the cost functions (the least any implementation needs, from shapes) ----


def admm_iterate_cost(sizes, info):
    """``info['iterations']`` iterations: each needs one feature pass
    over X, 2 n d D flop, and the four products of every block with the
    k columns of the state, 4 x 2 n D k, unpadded.  Compute-bound by the
    v5e's peaks.  A program that makes every block twice an iteration
    reads at most about half.  Bytes: X is read once an iteration."""
    n, d, k = sizes["rows"], sizes["d"], sizes["targets"]
    D = sizes["blocks"] * sizes["block_features"]
    it = info["iterations"]
    return it * (2.0 * n * d * D + 4 * 2.0 * n * D * k), it * 2.0 * n * d


def admm_factor_cost(sizes, info):
    """Once a call: one feature pass and the J Gram products
    ``Z_j Z_j'``, 2 n s_j^2 flop each.  Compute-bound."""
    n, d, J, s = sizes["rows"], sizes["d"], sizes["blocks"], sizes["block_features"]
    return 2.0 * n * d * J * s + J * 2.0 * n * s * s, 2.0 * n * d


COSTS = {"admm_iterate": admm_iterate_cost, "admm_factor": admm_factor_cost}


# -- the entry --------------------------------------------------------------


class Entry:
    def __init__(self, config, cell, seed, chips, tiny=False):
        self.sizes = {**config, **(config["rehearsal"] if tiny else {})}
        self.limits = cell["limits"]
        self.seed = seed
        self.timer = None  # the trainer keeps a phase timer of its own

    def setup(self):
        from libskylark_tpu import SketchContext, ml

        z = self.sizes
        self.X, self.Y = make_data(self.seed, z["rows"], z["d"], z["targets"],
                                   z["data_block"], jnp.dtype(z["feature_dtype"]))
        ctx = SketchContext(seed=z["sketch_seed"])
        kernel = ml.GaussianKernel(z["d"], z["sigma"])
        self.maps = [kernel.create_rft(z["block_features"], "regular", ctx)
                     for _ in range(z["blocks"])]
        jax.block_until_ready(self.Y)

    def step(self):
        from libskylark_tpu import ml

        z = self.sizes
        # the route is the code's own choice (by bytes) where the
        # configuration states none: the call is the one a user makes
        route = {} if z["cache_transforms"] is None else {
            "cache_transforms": z["cache_transforms"]}
        model = ml.BlockADMMSolver(
            z["loss"], z["regularizer"], self.maps,
            ml.ADMMParams(rho=z["rho"], lam=z["lam"], maxiter=z["maxiter"],
                          data_partitions=z["data_partitions"], **route),
        ).train(self.X, self.Y, classes=jnp.arange(z["targets"]))
        W = jax.block_until_ready(model.W)
        info = {key: model.info[key] for key in
                ("iterations", "feature_blocks", "transforms_cached", "feature_passes")}
        bad = None if info["transforms_cached"] == z["transforms_cached"] else (
            "the call took the other route: transforms_cached "
            f"{info['transforms_cached']}")
        return {"answer": pack(W, model.history), "units": {"solutions": 1},
                "info": info, "bad": bad}

    def release(self):
        pass  # the call returns its coefficients; its state died with it

    def draws(self):
        """Every map's W (s_j x d, scaled by 1/sigma) and phase shifts,
        read as data."""
        return ([m._underlying.realize(F32) for m in self.maps],
                [m.shifts(F32) for m in self.maps])

    def reference(self, sdtype=None, fdtype=None):
        return reference_train(self.X, self.Y, *self.draws(), self.sizes, sdtype, fdtype)

    def readings(self, answers):
        """[(pred_rel_err, obj_rel_err, label_agree)] of ``answers``."""
        z = self.sizes
        D, k = z["blocks"] * z["block_features"], z["targets"]
        if getattr(self, "_ref", None) is None:
            self._ref = jax.block_until_ready(self.reference())  # once a run
        Zs = sample_features(key_of(self.seed + 1), self.X, *self.draws(),
                             z["sample_rows"])
        ref = unpack(self._ref, D, k)
        # one answer a call: a stack would be a new program for every count
        return [[float(v) for v in compare(Zs, *unpack(a, D, k), *ref)] for a in answers]

    def check(self, answers):
        got = self.readings(answers)
        print(f"compared label_agree: {min(g[2] for g in got)!r} (not limited)",
              file=sys.stderr)
        return [(name, max(g[i] for g in got), self.limits[name])
                for i, name in enumerate(COMPARED)]

    def controls(self):
        """The reference in the precision below the configuration's, for
        what it states in each place: the state, the right-hand sides and
        the thin products in bfloat16 (they are float32), and the feature
        pipeline in fp8 e4m3 (it is bfloat16)."""
        return {"bf16_state": self.reference(sdtype=jnp.bfloat16),
                "fp8_features": self.reference(fdtype=jnp.float8_e4m3fn)}

    def control(self):
        """Of the two controls, the one that reads nearest its limits:
        every control has to come out as not correct."""
        nearest = None
        for name, answer in self.controls().items():
            r = self.readings([answer])[0]
            print(f"control {name}: pred_rel_err {r[0]!r} obj_rel_err {r[1]!r} "
                  f"label_agree {r[2]!r}", file=sys.stderr)
            over = max(r[i] / self.limits[n] for i, n in enumerate(COMPARED))
            if nearest is None or over < nearest[0]:
                nearest = (over, answer)
        return nearest[1]
