"""Entry kind ``ls_solve``: one dense tall least-squares solve a step.

The cell's ``entry.solver`` picks the program's entry point:
``blendenpik`` (``solvers.faster_least_squares``: sketch, QR, condition
estimate, preconditioned LSQR) or ``sketch_solve``
(``linalg.approximate_least_squares``: sketch, certificate, small QR).
The data are one problem from the configuration's ``data_seed`` with the
signs of its columns flipped from ``--seed``.  Every step rebuilds the sketch context
from the configuration's fixed ``sketch_seed``: every step of every run
draws the same sketch, because ``plans.apply`` keys its executable on
the serialized sketch and a new one is a new program (PERF.md section 6).

The plain reference is in this file and imports nothing of the program.
For ``blendenpik`` the answer is the least-squares solution itself,
whatever the sketch: normal equations by Cholesky, refined three times
on the f32 residual.  For ``sketch_solve`` the answer depends on the
sketch: the reference reads the FJLT's draws (signs and sample indices)
as data, applies the subsampled Hadamard matrix as a plain product in
row blocks, and solves the small system by QR.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = "highest"


def key_of(seed: int):
    """A PRNG key for any whole ``seed`` up to 64 bits (x64 is off)."""
    return jax.random.fold_in(jax.random.key(seed % 2**31), seed // 2**31 % 2**31)


def make_system(seed, data_seed, m, n, ratio, noise, block):
    """A0 = G * column scales log-spaced from 1 to 1/ratio and b = A0 x0 +
    noise from ``data_seed``; A = A0 with the signs of its columns flipped
    from ``seed`` (so the solution's signs flip with them).  Every seed is
    the same problem mirrored: R keeps its magnitudes, so the solver's
    condition estimate and LSQR's iteration count are the same whatever
    the seed.  With A0 itself drawn from the seed LSQR did 25 or 26
    iterations; with its columns permuted from the seed the 1-norm
    estimate passed the solver's threshold (PERF.md section 6).  Made on
    the device a row block at a time, in one program."""

    @jax.jit
    def gen(data_key, key):
        ka, kx, ke = jax.random.split(data_key, 3)
        scales = jnp.exp(-math.log(ratio) * jnp.arange(n, dtype=F32) / (n - 1))
        x0 = jax.random.normal(kx, (n,), F32) / scales
        sign = jax.random.rademacher(key, (n,), F32)

        def blk(k):
            A0 = jax.random.normal(k, (block, n), F32) * scales
            return A0 * sign, jnp.matmul(A0, x0, precision=HI)

        A, Ax0 = jax.lax.map(blk, jax.random.split(ka, m // block))
        b = Ax0.reshape(m) + noise * jax.random.normal(ke, (m,), F32)
        return A.reshape(m, n), b

    return gen(key_of(data_seed), key_of(seed))


# -- the plain reference ----------------------------------------------------


def lower(x, dtype):
    """x as the control holds it: rounded to ``dtype``, computed in f32."""
    return x if dtype is None else x.astype(dtype).astype(F32)


def reference_lstsq(A, b, block, dtype=None, refinements=3):
    """argmin |Ax - b|: Cholesky of A'A, then ``refinements`` steps on
    the residual, all in f32 at highest precision and in row blocks.
    ``dtype`` (the control) rounds A and b to a lower precision first."""

    @jax.jit
    def solve(A, b):
        m, n = A.shape
        A3, b2 = A.reshape(m // block, block, n), b.reshape(m // block, block)

        def gram(G, Ab):
            Ab = lower(Ab, dtype)
            return G + jnp.matmul(Ab.T, Ab, precision=HI), None

        def gradient(x):  # A'(b - A x)
            def fold(g, blk):
                Ab, bb = lower(blk[0], dtype), lower(blk[1], dtype)
                r = bb - jnp.matmul(Ab, x, precision=HI)
                return g + jnp.matmul(Ab.T, r, precision=HI), None

            return jax.lax.scan(fold, jnp.zeros((n,), F32), (A3, b2))[0]

        G = jax.lax.scan(gram, jnp.zeros((n, n), F32), A3)[0]
        L = jax.scipy.linalg.cho_factor(G, lower=True)
        x = jnp.zeros((n,), F32)
        for _ in range(1 + refinements):
            x = x + jax.scipy.linalg.cho_solve(L, gradient(x))
        return x

    return solve(A, b)


def reference_fjlt(A, b, signs, idx, block, dtype=None):
    """S [A b] for the FJLT with diagonal ``signs`` (m,) and sample
    indices ``idx`` (s,) over the Sylvester Hadamard matrix of size m:
    row i of S is ``(-1)^popcount(idx[i] & j) * signs[j] / sqrt(s)``."""

    @jax.jit
    def apply(A, b, signs, idx):
        m, n = A.shape
        Ab = jnp.concatenate([A, b[:, None]], axis=1).reshape(m // block, block, n + 1)
        D = signs.reshape(m // block, block)
        J = jnp.arange(m, dtype=jnp.int32).reshape(m // block, block)

        def fold(acc, blk):
            Ab_k, D_k, j = blk
            bits = jax.lax.population_count(idx[:, None] & j[None, :])
            H = (1 - 2 * (bits & 1)).astype(F32)
            return acc + jnp.matmul(
                H, lower(Ab_k * D_k[:, None], dtype), precision=HI), None

        acc = jnp.zeros((idx.shape[0], n + 1), F32)
        SAb = jax.lax.scan(fold, acc, (Ab, D, J))[0] / math.sqrt(idx.shape[0])
        return lower(SAb, dtype)

    SAb = apply(A, b, signs, idx)
    return SAb[:, :-1], SAb[:, -1]


@jax.jit
def small_lstsq(SA, Sb):
    Q, R = jnp.linalg.qr(SA)
    return jax.scipy.linalg.solve_triangular(
        R, jnp.matmul(Q.T, Sb, precision=HI), lower=False)


@jax.jit
def rel_err(x, ref):
    """||x - ref|| / ||ref||."""
    return jnp.linalg.norm(x - ref) / jnp.linalg.norm(ref)


# -- the cost functions (work the algorithm needs, from shapes) -------------


def lsqr_cost(sizes, info):
    """LSQR reads A twice an iteration (A v, A' u) in f32; memory-bound.
    Returns (flop, bytes) of one solve of ``info['lsqr_iters']`` iterations."""
    m, n, it = sizes["m"], sizes["n"], info["lsqr_iters"]
    return 4.0 * m * n * it, 2.0 * 4 * m * n * it


def fjlt_cost(sizes, info):
    """The sketch reads [A b] once and writes S [A b], in f32; its
    log2(m) butterfly adds per entry are far under the memory bound."""
    m, n, s = sizes["m"], sizes["n"] + 1, sizes["s"]
    return m * n * math.log2(m), 4.0 * (m * n + s * n)


COSTS = {"lsqr": lsqr_cost, "fjlt": fjlt_cost}


# -- the entry --------------------------------------------------------------


class Entry:
    def __init__(self, config, cell, seed, chips, tiny=False):
        self.sizes = z = {**config, **(config["rehearsal"] if tiny else {})}
        z["s"] = int(z["gamma"] * z["n"])
        self.solver = cell["entry"]["solver"]
        self.limits = cell["limits"]
        self.seed = seed
        self.timer = None  # the solvers take no phase timer

    def setup(self):
        z = self.sizes
        self.A, self.b = make_system(
            self.seed, z["data_seed"], z["m"], z["n"], z["column_scale_ratio"],
            z["noise"], z["block"])
        jax.block_until_ready(self.b)

    def step(self):
        from libskylark_tpu import SketchContext

        ctx = SketchContext(seed=self.sizes["sketch_seed"])
        info, bad = {}, None
        if self.solver == "blendenpik":
            from libskylark_tpu.solvers import (
                FasterLeastSquaresParams, faster_least_squares)

            x, raw = faster_least_squares(
                self.A, self.b, ctx, FasterLeastSquaresParams())
            jax.block_until_ready(x)
            info["lsqr_iters"] = int(raw["iterations"])
            if raw["attempts"] != 1 or "fallback" in raw:
                bad = f"recovery path: attempts {raw['attempts']}"
        else:
            from libskylark_tpu.linalg import approximate_least_squares

            x, raw = approximate_least_squares(
                self.A, self.b, ctx, return_info=True)
            jax.block_until_ready(x)
            pol = raw["policy"]
            if (pol["route"], pol["source"]) != ("sketch", "default"):
                bad = f"policy route {pol['route']} from {pol['source']}"
        att = raw["recovery"]["attempts"]
        if not bad and (len(att) != 1 or att[0]["verdict"] != "OK"):
            bad = "recovery path: " + ", ".join(
                f"{a['action']}={a['verdict']}" for a in att)
        return {"answer": x, "units": {"solutions": 1}, "info": info, "bad": bad}

    def release(self):
        pass  # a solve leaves no state behind; A and b are the data

    def draws(self):
        """The FJLT's random draws, read as data from a sketch object
        built like the program's own (same seed, same order)."""
        from libskylark_tpu import SketchContext
        from libskylark_tpu.sketch import FJLT

        z = self.sizes
        S = FJLT(z["m"], z["s"], SketchContext(seed=z["sketch_seed"]))
        return S._rfut.diagonal(F32), S.sample_indices

    def reference(self, dtype=None):
        z = self.sizes
        if self.solver == "blendenpik":
            return reference_lstsq(self.A, self.b, z["block"], dtype)
        signs, idx = self.draws()
        return small_lstsq(*reference_fjlt(
            self.A, self.b, signs, idx, z["ref_block"], dtype))

    def check(self, answers):
        # one answer a call: a stack would be a new program for every count
        ref = self.reference()
        err = max(float(rel_err(x, ref)) for x in answers)
        return [("x_rel_err", err, self.limits["x_rel_err"])]

    def control(self):
        """The reference in the precision below the configuration's
        (bfloat16 for float32), in the program's place."""
        return self.reference(jnp.bfloat16)
