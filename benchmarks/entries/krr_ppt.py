"""Entry kind ``krr_ppt``: one whole streamed polynomial-kernel ridge
training call a step (``ml.streaming_kernel_ridge`` with
``ml.PolynomialKernel``: TensorSketch features, ``sketch/ppt.py``).

X stays resident on the device and ``block_fn`` hands the trainer a
``dynamic_slice`` of it through ``block_args``, the data made as
``krr_train`` makes them.  The feature map is one chunk (``max_split =
2 s``): sweep 0 solves the ridge system and sweep 1 confirms it, five
panel passes a call, which the program reports as
``info["feature_passes"]``.  The map's draws come from the
configuration's fixed ``sketch_seed``: the trainer bakes them into its
three programs.

The plain reference is in this file and imports nothing of the program:
it reads the map's draws from the trained model as data (each level's
CountSketch buckets and signs, and the constant's bucket and sign),
makes the TensorSketch features itself with ``jnp.fft`` in complex64 a
row block at a time, solves the ridge system by Cholesky in f32 from
sums over row blocks, and compares predictions on a sample of the
training rows drawn from the seed, with its own features under the
program's coefficients.
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
    linear teacher's classes, made a row block at a time in one program
    (``krr_train``'s data)."""

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
    """x as the control holds it: rounded to ``dtype``, computed in f32;
    a complex x part by part."""
    if dtype is None:
        return x
    if jnp.iscomplexobj(x):
        return jax.lax.complex(lower(x.real, dtype), lower(x.imag, dtype))
    return x.astype(dtype).astype(F32)


def hash_matrices(buckets, signs, d, s):
    """(q, d, s) f32: level l's CountSketch as a matrix, one signed 1 a
    row, at its bucket."""
    return jnp.where(buckets[:, :, None] == jnp.arange(s), signs[:, :, None], 0.0)


def dft_tables(s, dtype):
    """(cos, sin) of 2 pi jk / s, (s, s) f32 rounded to ``dtype``: the
    control's transforms, as a chip pipeline in ``dtype`` would hold
    them (a fixed rounding, the same for every row)."""
    j = jnp.arange(s, dtype=jnp.int32)
    theta = (2.0 * math.pi / s) * ((j[:, None] * j[None, :]) % s).astype(F32)
    return lower(jnp.cos(theta), dtype), lower(jnp.sin(theta), dtype)


def features(X, H, idx, val, gamma, c, dtype=None):
    """TensorSketch features of the rows of X (k, d): for each level the
    CountSketch of sqrt(gamma) x plus sqrt(c) s_l at bucket h_l, its
    complex FFT, the levels' product, the inverse FFT's real part.  The
    control holds in ``dtype`` what a pipeline in it holds: the rows,
    each level's sketch, the transforms' tables (it transforms by
    products with them: forward W (C - i S), inverse Re(P (C + i S)) / s),
    each transform's output, the product and the features."""
    s = H.shape[2]
    tables = None if dtype is None else dft_tables(s, dtype)
    P = None
    for l in range(H.shape[0]):
        W = math.sqrt(gamma) * jnp.matmul(lower(X.astype(F32), dtype), H[l], precision=HI)
        W = lower(W.at[:, idx[l]].add(math.sqrt(c) * val[l]), dtype)
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


def reference_ridge(X, Y, H, idx, val, gamma, c, lam, block, dtype=None):
    """(Z'Z + lam I) C = Z'Y over row blocks, solved by Cholesky."""

    @jax.jit
    def solve(X, Y, H, idx, val):
        n, d = X.shape
        s, t = H.shape[2], Y.shape[1]

        def fold(carry, blk):
            Z = features(blk[0], H, idx, val, gamma, c, dtype)
            return (carry[0] + jnp.matmul(Z.T, Z, precision=HI),
                    carry[1] + jnp.matmul(Z.T, blk[1], precision=HI)), None

        zero = (lam * jnp.eye(s, dtype=F32), jnp.zeros((s, t), F32))
        G, rhs = jax.lax.scan(
            fold, zero,
            (X.reshape(n // block, block, d), Y.reshape(n // block, block, t)))[0]
        return jax.scipy.linalg.cho_solve(
            jax.scipy.linalg.cho_factor(G, lower=True), rhs)

    return solve(X, Y, H, idx, val)


@partial(jax.jit, static_argnames=("k", "gamma", "c"))
def sample_features(key, X, H, idx, val, k, gamma, c):
    """The reference's features of k training rows drawn from ``key``."""
    return features(X[jax.random.randint(key, (k,), 0, X.shape[0])], H, idx, val,
                    gamma, c)


@jax.jit
def prediction_err(Zs, C, C_ref):
    """||Zs C - Zs C_ref||_F / ||Zs C_ref||_F."""
    ref = jnp.matmul(Zs, C_ref, precision=HI)
    return jnp.linalg.norm(jnp.matmul(Zs, C, precision=HI) - ref) / jnp.linalg.norm(ref)


# -- the cost function ------------------------------------------------------


def ppt_features_cost(sizes, info):
    """Flop and bytes the TensorSketch features of a call need, whatever
    implements them: a row a pass, the q CountSketches (2 q d), q forward
    transforms and one inverse counted as real-input FFTs (2.5 S log2 S
    each), the q - 1 level products of S/2 + 1 complex numbers (6 flop
    each); X read once (2 d bytes in bf16).  Times the rows and the
    passes the call made (``info["feature_passes"]``).  The bf16 DFT the
    program runs does 2 S^2 a transform and part, so its share reads low:
    a floor."""
    n, d, s, q = sizes["rows"], sizes["d"], sizes["s"], sizes["q"]
    row = 2.0 * q * d + (q + 1) * 2.5 * s * math.log2(s) + 6.0 * (q - 1) * (s // 2 + 1)
    passes = info["feature_passes"]
    return passes * n * row, passes * n * 2.0 * d


COSTS = {"ppt_features": ppt_features_cost}


# -- the entry --------------------------------------------------------------


class Entry:
    def __init__(self, config, cell, seed, chips, tiny=False):
        self.sizes = {**config, **(config["rehearsal"] if tiny else {})}
        self.limits = cell["limits"]
        self.seed = seed
        self.timer = None  # a PhaseTimer in the traced run only

    def kernel(self):
        from libskylark_tpu import ml

        z = self.sizes
        return ml.PolynomialKernel(z["d"], q=z["q"], c=z["c"], gamma=z["gamma"])

    def setup(self):
        from libskylark_tpu import SketchContext

        z = self.sizes
        self.dtype = jnp.dtype(z["feature_dtype"])
        # The cell measures the map with its operands built once a program
        # and the passes the call reports: a program whose map hoists
        # nothing is refused here, before minutes of compilation.
        probe = self.kernel().create_rft(z["s"], "regular", SketchContext(seed=0))
        if probe.hoistable_operands(self.dtype) is None:
            raise RuntimeError(f"{type(probe).__name__} of this program hoists no "
                               "operands: the cell needs the map of PR 39")
        self.X, self.Y = make_data(
            self.seed, z["rows"], z["d"], z["targets"], z["block_rows"], self.dtype)
        jax.block_until_ready(self.Y)

    def step(self):
        from libskylark_tpu import SketchContext, ml

        z = self.sizes
        model = ml.streaming_kernel_ridge(
            self.kernel(), block_fn, (z["rows"], z["d"]), self.Y, z["lam"], z["s"],
            SketchContext(seed=z["sketch_seed"]),
            ml.KrrParams(max_split=2 * z["s"], iter_lim=z["sweeps"]),
            block_rows=z["block_rows"], feature_dtype=self.dtype,
            block_args=(self.X,), timer=self.timer,
        )
        jax.block_until_ready(model.W)
        self.model = model
        info = dict(model.info)
        bad = None if info["feature_passes"] == 1 + 2 * z["sweeps"] else (
            f"{info['feature_passes']} feature passes, not {1 + 2 * z['sweeps']}")
        return {"answer": model.W, "units": {"rows": z["rows"] * z["sweeps"]},
                "info": info, "bad": bad}

    def release(self):
        """Keep the map's draws (data), drop the trained model."""
        M, z = self.model.maps[0], self.sizes
        assert (M.q, M.c, M.gamma) == (z["q"], z["c"], z["gamma"])
        self.H = hash_matrices(jnp.stack([w.buckets() for w in M._cwts]),
                               jnp.stack([w.values(F32) for w in M._cwts]), z["d"], z["s"])
        self.idx, self.val = M._hash_consts(F32)
        del self.model

    def reference(self, levels=None, dtype=None):
        """The reference's coefficients; ``levels`` keeps the first so
        many levels (a planted fault), ``dtype`` is the control's."""
        z = self.sizes
        H = self.H if levels is None else self.H[:levels]
        return reference_ridge(self.X, self.Y, H, self.idx, self.val, z["gamma"],
                               z["c"], z["lam"], z["ref_block"], dtype)

    def check(self, answers):
        z = self.sizes
        C_ref = self.reference()
        Zs = sample_features(key_of(self.seed + 1), self.X, self.H, self.idx, self.val,
                             z["sample_rows"], z["gamma"], z["c"])
        # one answer a call: a stack would be a new program for every count
        err = max(float(prediction_err(Zs, C, C_ref)) for C in answers)
        return [("pred_rel_err", err, self.limits["pred_rel_err"])]

    def control(self):
        """The reference in the precision below the configuration's
        (fp8 e4m3 for bfloat16 features), in the program's place."""
        return self.reference(dtype=jnp.float8_e4m3fn)
