"""Entry kind ``krr_train``: one whole streamed random-feature kernel
ridge training call a step (``ml.streaming_kernel_ridge``).

X stays resident on the device and ``block_fn`` hands the trainer a
``dynamic_slice`` of it through ``block_args``: real data at a real
size, and no loop-invariant panel for XLA to hoist.  The feature map is
one chunk (``max_split = 2 s``), so sweep 0 solves the ridge system and
sweep 1 confirms it: two sweeps a call, every call.  The data come from
``--seed``; the feature map's draws come from the configuration's fixed
``sketch_seed``, because the trainer bakes them into its three programs
as constants: a new sketch seed is a new program, 8 s of compilation
each on the chip (PERF.md section 6).

The plain reference is in this file and imports nothing of the program:
it reads the feature map's draws (W and the phase shifts) from the
trained model as data, makes the features itself in f32 at highest
precision a row block at a time, solves the ridge system by Cholesky and
compares predictions on a sample of the training rows drawn from the
seed, with its own features under the program's coefficients.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = "highest"


def key_of(seed: int):
    """A PRNG key for any whole ``seed`` up to 64 bits (x64 is off)."""
    return jax.random.fold_in(jax.random.key(seed % 2**31), seed // 2**31 % 2**31)


def make_data(seed, rows, d, targets, block, dtype):
    """X ~ N(0, 1) in ``dtype`` and the +-1 one-vs-all codes of a seeded
    linear teacher's classes, made a row block at a time in one program."""

    @jax.jit
    def gen(key):
        kx, kt = jax.random.split(key)
        T = jax.random.normal(kt, (d, targets), F32)

        def blk(k):
            X = jax.random.normal(k, (block, d), F32).astype(dtype)
            cls = jnp.argmax(jnp.matmul(X.astype(F32), T, precision=HI), axis=1)
            return X, jnp.where(cls[:, None] == jnp.arange(targets), 1.0, -1.0)

        X, Y = jax.lax.map(blk, jax.random.split(kx, rows // block))
        return X.reshape(rows, d), Y.reshape(rows, targets).astype(F32)

    return gen(key_of(seed))


def block_fn(start, rows, X):
    return jax.lax.dynamic_slice_in_dim(X, start, rows, axis=0)


# -- the plain reference ----------------------------------------------------


def lower(x, dtype):
    """x as the control holds it: rounded to ``dtype``, computed in f32."""
    return x if dtype is None else x.astype(dtype).astype(F32)


def features(X, W, shifts, dtype=None):
    """sqrt(2/s) cos(X W' + shifts), f32 at highest precision.  The
    control holds what a feature pipeline in ``dtype`` holds in it: X,
    W, the product, the phase and the features (the sums stay f32)."""
    s = W.shape[0]
    WX = jnp.matmul(lower(X.astype(F32), dtype), lower(W, dtype).T, precision=HI)
    phase = lower(lower(WX, dtype) + lower(shifts, dtype), dtype)
    return lower(math.sqrt(2.0 / s) * jnp.cos(phase), dtype)


def reference_ridge(X, Y, W, shifts, lam, block, dtype=None):
    """(Z'Z + lam I) C = Z'Y over row blocks, solved by Cholesky."""

    @jax.jit
    def solve(X, Y, W, shifts):
        n, d = X.shape
        s, t = W.shape[0], Y.shape[1]

        def fold(carry, blk):
            Z = features(blk[0], W, shifts, dtype)
            return (carry[0] + jnp.matmul(Z.T, Z, precision=HI),
                    carry[1] + jnp.matmul(Z.T, blk[1], precision=HI)), None

        zero = (lam * jnp.eye(s, dtype=F32), jnp.zeros((s, t), F32))
        G, c = jax.lax.scan(
            fold, zero,
            (X.reshape(n // block, block, d), Y.reshape(n // block, block, t)))[0]
        return jax.scipy.linalg.cho_solve(
            jax.scipy.linalg.cho_factor(G, lower=True), c)

    return solve(X, Y, W, shifts)


@partial(jax.jit, static_argnames=("k",))
def sample_features(key, X, W, shifts, k):
    """The reference's features of k training rows drawn from ``key``."""
    return features(X[jax.random.randint(key, (k,), 0, X.shape[0])], W, shifts)


@jax.jit
def prediction_err(Zs, C, C_ref):
    """||Zs C - Zs C_ref||_F / ||Zs C_ref||_F."""
    ref = jnp.matmul(Zs, C_ref, precision=HI)
    return jnp.linalg.norm(jnp.matmul(Zs, C, precision=HI) - ref) / jnp.linalg.norm(ref)


# -- the cost function ------------------------------------------------------


def krr_cost(sizes, info):
    """Flop one training call needs with bf16 features (compute-bound):
    a feature panel pass is 2 n d s; sweep 0 makes three passes (Gram,
    Z'R, R update), each later sweep two; the Gram is 2 n s^2; each Z'R
    and each R update is 2 n s t.  Bytes: X is read once a pass."""
    n, d, s, t = sizes["rows"], sizes["d"], sizes["s"], sizes["targets"]
    sweeps = sizes["sweeps"]
    passes = 1 + 2 * sweeps
    flop = passes * 2.0 * n * d * s + 2.0 * n * s * s + 2 * sweeps * 2.0 * n * s * t
    return flop, passes * 2.0 * n * d


COSTS = {"krr": krr_cost}


# -- the entry --------------------------------------------------------------


class Entry:
    def __init__(self, config, cell, seed, chips, tiny=False):
        self.sizes = {**config, **(config["rehearsal"] if tiny else {})}
        self.limits = cell["limits"]
        self.seed = seed
        self.timer = None  # a PhaseTimer in the traced run only

    def setup(self):
        z = self.sizes
        self.dtype = jnp.dtype(z["feature_dtype"])
        self.X, self.Y = make_data(
            self.seed, z["rows"], z["d"], z["targets"], z["block_rows"], self.dtype)
        jax.block_until_ready(self.Y)

    def step(self):
        from libskylark_tpu import SketchContext, ml

        z = self.sizes
        model = ml.streaming_kernel_ridge(
            ml.GaussianKernel(z["d"], sigma=z["sigma"]), block_fn,
            (z["rows"], z["d"]), self.Y, z["lam"], z["s"],
            SketchContext(seed=z["sketch_seed"]),
            ml.KrrParams(max_split=2 * z["s"], iter_lim=z["sweeps"]),
            block_rows=z["block_rows"], feature_dtype=self.dtype,
            block_args=(self.X,), timer=self.timer,
        )
        jax.block_until_ready(model.W)
        self.model = model
        return {"answer": model.W, "units": {"rows": z["rows"] * z["sweeps"]},
                "info": {}, "bad": None}

    def release(self):
        """Keep the feature map's draws (data), drop the trained model."""
        rft = self.model.maps[0]
        self.W = rft._underlying.realize(F32)  # (s, d), scaled by 1/sigma
        self.shifts = rft.shifts(F32)
        del self.model

    def check(self, answers):
        z = self.sizes
        C_ref = reference_ridge(self.X, self.Y, self.W, self.shifts, z["lam"],
                                z["ref_block"])
        Zs = sample_features(key_of(self.seed + 1), self.X, self.W, self.shifts,
                             z["sample_rows"])
        # one answer a call: a stack would be a new program for every count
        err = max(float(prediction_err(Zs, C, C_ref)) for C in answers)
        return [("pred_rel_err", err, self.limits["pred_rel_err"])]

    def control(self):
        """The reference in the precision below the configuration's
        (fp8 e4m3 for bfloat16 features), in the program's place."""
        z = self.sizes
        return reference_ridge(self.X, self.Y, self.W, self.shifts, z["lam"],
                               z["ref_block"], jnp.float8_e4m3fn)
