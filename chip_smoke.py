#!/usr/bin/env python3
"""Chip smoke: sketch -> solve -> train -> serve on one TPU chip.

Drives the library's main path once, through the entry points users
call, at the sizes of the repository's flagship rows, and checks every
result against a plain reference.  One process; x64 off; data from
``--seed``.  Exits non-zero unless JAX's first device is a TPU.

    python chip_smoke.py              # one chip, phases 1-4
    python chip_smoke.py --chips 4    # the sharded paths and their
                                      # one-chip comparisons, nothing else

Every line of standard output is one JSON object.  Phase lines carry the
phase's seconds, compile counts (``plans.stats()`` and JAX's own compile
requests / persistent-cache hits), the route each kernel took, the
checks as ``name: [value, bound]`` and the device's peak bytes.  The
last line is ``{"ok": true, "device": {...}}``.

``--rehearse`` (never given by the driver) runs the same control flow at
a tiny size wherever JAX puts it — on the CPU with the Pallas kernels in
interpret mode — for ``tests/test_chip_smoke.py``; it skips the compiled
hardware guards and says ``"rehearsal": true`` on its last line.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
_T0 = time.perf_counter()

# The run must end inside 1200 s, compilation included, and a cold run is
# nearly all compilation on the host's CPU cores, which a one-chip
# machine shares: the same tree took 698 s cold in one call and 1086 s
# in another (my chip runs, PR 21).  The eleven guards are about 330
# one-op compiles.  When this many seconds are gone before they start
# (about 110 on the quick host, about 250 on the slow one) only the
# guards of compiled kernels run; the cut is listed on the phase's line.
GUARDS_CUT_AFTER_S = 180.0
KERNEL_GUARDS = frozenset({
    "rfut_rowwise_compiled", "pallas_window_compiled",
    "fjlt_two_step_kernel", "fjlt_pallas_branch_compiled",
})


def select_guards(names, elapsed_s: float):
    """(to run, cut) of the guard names at ``elapsed_s`` into the run."""
    if elapsed_s <= GUARDS_CUT_AFTER_S:
        return list(names), []
    return ([n for n in names if n in KERNEL_GUARDS],
            [n for n in names if n not in KERNEL_GUARDS])

# Shapes of the only chip rows the repository has (sketches
# 131072x4096->1024), the bench's solve / ridge / ADMM rows, the
# north-star streaming KRR at a tenth of its 10M rows, and MNIST-8M's
# width (BASELINE.json) for the CLI file.
REAL = dict(
    sk_m=131_072, sk_n=4096, sk_s=1024, stream_chunks=4,
    ls_m=262_144, ls_n=1024,
    krr_m=262_144, krr_d=4096, krr_s=2048, krr_test=4096,
    skrr_n=1_048_576, skrr_d=4096, skrr_s=2048, skrr_block=131_072,
    admm_m=262_144, admm_d=128, admm_s=2048,
    cli_m=65_536, cli_d=784, cli_s=1024,
    svd_rank=16,
)
TINY = dict(
    sk_m=1024, sk_n=256, sk_s=128, stream_chunks=4,
    ls_m=2048, ls_n=32,
    krr_m=1024, krr_d=64, krr_s=128, krr_test=64,
    skrr_n=2048, skrr_d=64, skrr_s=128, skrr_block=512,
    admm_m=1024, admm_d=16, admm_s=64,
    cli_m=512, cli_d=24, cli_s=64,
    svd_rank=4,
)


class SmokeFailure(AssertionError):
    pass


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


class CompileCounter:
    """JAX's own compile events: every jit-cache miss that reaches the
    backend is a ``request``; a ``hit`` was served by the persistent
    cache; ``seconds`` is the time spent in either, also kept by the
    compiled function's name so a phase can name its slowest."""

    def __init__(self):

        self.requests = self.hits = 0
        self.seconds = 0.0
        self.by_name: dict = {}
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, name, secs, fun_name=None, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            n, t = self.by_name.get(fun_name, (0, 0.0))
            self.by_name[fun_name] = (n + 1, t + secs)

    def slowest(self, since: dict, k: int = 6) -> list:
        """[name, compiles, seconds] of the k names that took longest
        since the ``since`` copy of :attr:`by_name`."""
        rows = []
        for name, (n, t) in self.by_name.items():
            n0, t0 = since.get(name, (0, 0.0))
            if t - t0 > 0:
                rows.append([name, n - n0, round(t - t0, 2)])
        return sorted(rows, key=lambda r: -r[2])[:k]

    def snapshot(self):
        return (self.requests, self.hits, self.seconds)


class Phase:
    """One output line: timing and compile deltas around the body, the
    checks recorded by :meth:`check`.  The line is printed when the body
    ends; a check outside its bound then raises.  An exception from the
    body itself is not caught."""

    def __init__(self, name: str, counter: CompileCounter, **fields):
        self.name, self.counter = name, counter
        self.fields = dict(fields)
        self.checks: dict = {}
        self.errors: list[float] = []
        self.failed: list[str] = []

    def __enter__(self):
        from libskylark_tpu import plans

        self._plans0 = plans.stats()
        self._c0 = self.counter.snapshot()
        self._names0 = dict(self.counter.by_name)
        self._t0 = time.perf_counter()
        return self

    def check(self, name: str, value: float, bound: float,
              error: bool = True) -> None:
        """``value <= bound`` or the phase fails.  ``error=False`` for a
        ratio or a count, which is no error against a reference and
        stays out of the line's ``max_error``."""
        value = float(value)
        self.checks[name] = [value, bound]
        if error:
            self.errors.append(value)
        if not value <= bound:  # NaN fails
            self.failed.append(f"{name}: {value} > {bound}")

    def require(self, name: str, ok: bool) -> None:
        self.checks[name] = bool(ok)
        if not ok:
            self.failed.append(name)

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False

        from libskylark_tpu import plans

        st, c1 = plans.stats(), self.counter.snapshot()
        mem = jax.devices()[0].memory_stats() or {}
        emit({
            "phase": self.name,
            "seconds": round(time.perf_counter() - self._t0, 3),
            "plan_compiles": st["compiles"] - self._plans0["compiles"],
            "plan_compile_seconds": round(
                st["compile_seconds"] - self._plans0["compile_seconds"], 3
            ),
            "compile_requests": c1[0] - self._c0[0],
            "cache_hits": c1[1] - self._c0[1],
            "compile_seconds": round(c1[2] - self._c0[2], 3),
            "slowest_compiles": self.counter.slowest(self._names0),
            "max_error": max(self.errors, default=None),
            "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
            **self.fields,
            "checks": self.checks,
        })
        if self.failed:
            raise SmokeFailure(f"{self.name}: " + "; ".join(self.failed))
        return False


# The script's own reference code runs jitted: an eager op is one compile
# each on the chip (about a third of a second), and the references would
# be hundreds of them.


@jax.jit
def _rel_err_dev(out, ref):
    out = jnp.asarray(out, jnp.float32).reshape(-1)
    ref = jnp.asarray(ref, jnp.float32).reshape(-1)
    return jnp.max(jnp.abs(out - ref)) / jnp.maximum(
        jnp.max(jnp.abs(ref)), 1e-30
    )


def _rel_err(out, ref) -> float:
    """max|out - ref| / max|ref| in float32."""
    return float(_rel_err_dev(out, ref))


@jax.jit
def _mm(a, b):
    """The plain reference product: float32, highest precision."""
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision="highest")


@jax.jit
def _plain_ridge(Z, Y, lam):
    """(Z'Z + lam I) W = Z'Y in float32 at highest precision, by
    Cholesky."""
    Z = Z.astype(jnp.float32)
    G = jnp.matmul(Z.T, Z, precision="highest")
    G = G + lam * jnp.eye(Z.shape[1], dtype=jnp.float32)
    c = jnp.matmul(Z.T, Y.astype(jnp.float32), precision="highest")
    return jax.scipy.linalg.cho_solve(
        jax.scipy.linalg.cho_factor(G, lower=True), c
    )


_normal_block = jax.jit(jax.random.normal, static_argnums=(1, 2))


def _normal(key, shape, dtype, block_rows: int = 16_384):
    """Standard normals of ``shape``, made on the device a row block at a
    time: one generator of (block_rows, cols) compiles in seconds where
    one of the whole array took 16-20 s at a billion entries."""
    if len(shape) < 2 or shape[0] <= block_rows:
        return jax.random.normal(key, shape, dtype)
    assert shape[0] % block_rows == 0, shape
    keys = jax.random.split(key, shape[0] // block_rows)
    return jnp.concatenate([
        _normal_block(k, (block_rows,) + tuple(shape[1:]), dtype)
        for k in keys
    ])


# ---------------------------------------------------------------------------
# routes
# ---------------------------------------------------------------------------


def hash_route(S, batch: int, dtype) -> str:
    """The branch ``HashSketch._apply_dense`` takes for a full
    columnwise apply with ``batch`` columns."""
    from libskylark_tpu.sketch import hash as hash_mod

    if S.n * S.s <= S._ONEHOT_LIMIT and batch >= 16:
        return "gemm"
    return hash_mod._window_mode(S.n, batch, S.s, dtype, S.nnz)


def fjlt_route(S, rows: int, dtype) -> str:
    """The branch ``FJLT.apply`` takes rowwise on (rows, n) input."""
    from libskylark_tpu.sketch import fjlt as fjlt_mod
    from libskylark_tpu.sketch import pallas_fut

    if S._gemm_wins(dtype):
        return "gemm"
    if fjlt_mod._use_pallas() and pallas_fut.supported(rows, S.n, S._nb):
        return "kernel"
    return "xla"


# ---------------------------------------------------------------------------
# phase 1: sketch
# ---------------------------------------------------------------------------


def phase_sketch(cfg, seed, counter, rehearse):
    from scipy.linalg import hadamard

    from libskylark_tpu import SketchContext, plans, streaming
    from libskylark_tpu.sketch import CWT, FJLT, JLT, MMT
    from libskylark_tpu.sketch import fjlt as fjlt_mod
    from libskylark_tpu.sketch import hash as hash_mod
    from libskylark_tpu.sketch import pallas_window

    m, n, s = cfg["sk_m"], cfg["sk_n"], cfg["sk_s"]
    f32, bf16 = jnp.float32, jnp.bfloat16
    with Phase("sketch", counter, shape=f"{m}x{n}->{s}") as ph:
        routes = ph.fields.setdefault("routes", {})
        A = _normal(jax.random.PRNGKey(seed), (m, n), f32)
        A16 = A.astype(bf16)

        # JLT bf16 rowwise.  Omega and the output are both rounded to
        # bf16 (2^-9 each, relative); the sum over n terms keeps the
        # relative error of a term, so 4 * 2^-9 of the output scale
        # bounds the maximum over all entries with room.
        S = JLT(n, s, SketchContext(seed=seed + 1))
        out = plans.apply(S, A16, "rowwise")
        ph.require("jlt_bf16_shape", out.shape == (m, s) and out.dtype == bf16)
        ph.check("jlt_bf16_rowwise", _rel_err(out, _mm(A16, S.realize(f32).T)),
                 4 * 2.0 ** -9)
        routes["JLT bf16 rowwise"] = "gemm"
        del out
        gc.collect()

        # CWT / MMT f32 columnwise.  Values are +-1 (CWT) so every
        # product is exact; library and reference differ only in f32
        # summation order over the ~m/s terms of a bucket: 1e-5 of the
        # output scale.  MMT's Cauchy values ride the bf16-exact 0/1
        # matrix with A scaled first — same argument, the established
        # hardware bar is 5e-5 (tests/_hw_guards.py).
        for name, cls, bound in (("CWT", CWT, 1e-5), ("MMT", MMT, 5e-5)):
            S = cls(m, s, SketchContext(seed=seed + 2))
            out = plans.apply(S, A, "columnwise")
            ph.require(f"{name.lower()}_shape", out.shape == (s, n))
            ref = _mm(S._hash_matrix(f32).T, A)
            ph.check(f"{name.lower()}_f32_columnwise", _rel_err(out, ref),
                     bound)
            routes[f"{name} f32 columnwise"] = hash_route(S, n, f32)
            del out, ref
            gc.collect()

        # FJLT rowwise, f32 and bf16.  Reference: the sampled, sign-
        # flipped Hadamard columns as a dense (n, s) matrix.  All
        # products are +-a (exact); f32: summation order only, 1e-5.
        # bf16: the same f32 accumulation, then one rounding of the
        # output to bf16, 2^-9 relative — bound 2^-8 of the scale.
        S = FJLT(n, s, SketchContext(seed=seed + 3))
        nb = S._nb
        idx = np.asarray(S.sample_indices)
        G = jnp.asarray(hadamard(nb, dtype=np.int8)[:n][:, idx], f32)
        G = G * S._rfut.diagonal(f32)[:, None] / np.sqrt(s)
        for dt, X, bound in ((f32, A, 1e-5), (bf16, A16, 2.0 ** -8)):
            out = plans.apply(S, X, "rowwise")
            ph.require(f"fjlt_{dt.__name__}_shape", out.shape == (m, s))
            ph.check(f"fjlt_{dt.__name__}_rowwise", _rel_err(out, _mm(X, G)),
                     bound)
            routes[f"FJLT {dt.__name__} rowwise"] = fjlt_route(S, m, dt)
            del out
        del G, A16, A
        gc.collect()

        # One streaming pass of CWT over host-fed chunks: the fused
        # acc= chunk step and the prefetcher.  Same bound as CWT above
        # (the accumulator adds one more f32 add per chunk).
        nchunks = cfg["stream_chunks"]
        keys = jax.random.split(jax.random.PRNGKey(seed + 4), nchunks)
        host = [np.asarray(_normal(k, (m, n), f32)) for k in keys]
        S = CWT(m * nchunks, s, SketchContext(seed=seed + 5))
        out = streaming.sketch(lambda start: iter(host[start:]), S, ncols=n)
        out = jax.block_until_ready(out)
        ph.require("stream_shape", out.shape == (s, n))
        M = S._hash_matrix(f32)
        ref = jnp.zeros((s, n), f32)
        for c in range(nchunks):
            # One chunk on the device at a time: dispatched all at once
            # (a warm run does not pause to compile) the four 2 GB
            # chunks took the peak to 15.7 of 16.9 GB.
            ref = ref + _mm(M[c * m:(c + 1) * m].T, jnp.asarray(host[c]))
            ref.block_until_ready()
        ph.check("cwt_stream_columnwise", _rel_err(out, ref), 1e-5)
        routes[f"CWT stream chunk {m}x{n}"] = hash_mod._window_mode(
            m, n, s, f32, 1
        )
        routes["FJLT columnwise gather (serve systems)"] = (
            fjlt_mod._gather_mode(nb, s, n, f32)
        )
        del out, ref, M, host
        gc.collect()

        # Kernel self-checks, compiled, against XLA on random data.
        interp = rehearse
        ph.check("window_self_check",
                 pallas_window.self_check(interpret=interp), 1e-5)
        ph.check("window_self_check_nnz4",
                 pallas_window.self_check(interpret=interp, nnz=4), 1e-5)
        ph.check("gather_self_check",
                 pallas_window.self_check_gather(interpret=interp), 0.0)

        if rehearse:
            ph.fields["guards"] = "skipped: rehearsal (compiled kernels)"
        else:
            sys.path.insert(0, os.path.join(REPO, "tests"))
            import _hw_guards

            guards = dict(_hw_guards.GUARDS)
            elapsed = time.perf_counter() - _T0
            run_now, cut = select_guards(list(guards), elapsed)
            for name in run_now:
                guards[name]()  # raises on failure
            ph.fields["guards"] = run_now
            if cut:
                ph.fields["guards_cut"] = {
                    "names": cut,
                    "why": f"{elapsed:.0f} s gone before the guards (over "
                           f"{GUARDS_CUT_AFTER_S:.0f}): a slow host; cut to "
                           "keep the cold run inside 1200 s",
                }


# ---------------------------------------------------------------------------
# phase 2: solve
# ---------------------------------------------------------------------------


def make_system(cfg, seed):

    m, n = cfg["ls_m"], cfg["ls_n"]
    ka, kx, ke = jax.random.split(jax.random.PRNGKey(seed + 10), 3)
    A = _normal(ka, (m, n), jnp.float32)
    x = jax.random.normal(kx, (n,), jnp.float32)
    noise = jax.random.normal(ke, (m,), jnp.float32)
    return A, jax.jit(lambda A, x, e: A @ x + 0.1 * e)(A, x, noise)


def phase_solve(cfg, seed, counter):

    from libskylark_tpu import SketchContext, linalg

    m, n = cfg["ls_m"], cfg["ls_n"]
    with Phase("solve", counter, shape=f"{m}x{n}") as ph:
        A, b = make_system(cfg, seed)

        def resid(x):
            return float(jnp.linalg.norm(_mm(A, jnp.asarray(x)) - b))

        x_e = linalg.exact_least_squares(A, b)
        r_e = resid(x_e)
        x_a, info = linalg.approximate_least_squares(
            A, b, SketchContext(seed=seed + 11), return_info=True
        )
        ph.fields["approximate_info"] = {
            k: info[k] for k in ("policy", "recovery") if k in info
        }
        ph.require("approximate_finite", bool(jnp.all(jnp.isfinite(x_a))))
        # Sketch-and-solve at the default s = 4n: the expected residual
        # ratio of a subspace embedding is sqrt(1 + n/(s-n)) = 1.155;
        # 1.5 leaves room for the FJLT's sampling variance.
        ph.check("approximate_residual_ratio", resid(x_a) / r_e, 1.5,
                 error=False)
        x_f = linalg.faster_least_squares(A, b, SketchContext(seed=seed + 12))
        if isinstance(x_f, tuple):
            x_f = x_f[0]
        # Sketch-preconditioned LSQR iterates to the optimum: f32
        # round-off of the iteration only.
        ph.check("faster_residual_ratio", resid(x_f) / r_e, 1.0 + 1e-3,
                 error=False)
    return A, b


# ---------------------------------------------------------------------------
# phase 3: train
# ---------------------------------------------------------------------------


def phase_train(cfg, seed, counter):

    from libskylark_tpu import SketchContext, ml, native
    from libskylark_tpu.cli import krr as cli_krr
    from libskylark_tpu.io import write_hdf5

    f32, bf16 = jnp.float32, jnp.bfloat16
    emit({
        "phase": "native",
        "native_parser_available": bool(native.available()),
        "cli_file_reader": "h5py (hdf5_dense is not text: neither the "
                           "native LIBSVM parser nor its Python fallback "
                           "reads it)",
    })
    with Phase("train", counter) as ph:
        # -- approximate KRR, the bench's ridge row (bf16 inputs) --------
        m, d, s, mt = (cfg[k] for k in ("krr_m", "krr_d", "krr_s", "krr_test"))
        kx, ky = jax.random.split(jax.random.PRNGKey(seed + 20))
        X = _normal(kx, (m, d), bf16)
        Y = jax.random.normal(ky, (m, 1), f32)
        lam = 0.1
        model = ml.approximate_kernel_ridge(
            ml.GaussianKernel(d, sigma=4.0), X, Y, lam, s,
            SketchContext(seed=seed + 21),
        )
        ph.fields["krr"] = f"{m}x{d}->{s} bf16"
        ph.require("krr_finite", bool(jnp.all(jnp.isfinite(model.W))))
        # Reference: plain f32 ridge on the library's own features.  The
        # model's coefficients and predictions are bf16 (the feature
        # dtype): 2^-9 each on a sum of 2048 terms that largely cancel
        # (the targets are noise, the predictions small): bound 2^-5 of
        # the prediction scale (3.3e-3 measured on the chip).
        features = jax.jit(lambda X: model.features(X))
        W_ref = _plain_ridge(features(X), Y, lam)
        Xt = X[:mt]
        pred = jax.jit(lambda X: model.predict(X))(Xt)
        ph.check("krr_predict_vs_plain_ridge",
                 _rel_err(pred, _mm(features(Xt), W_ref)), 2.0 ** -5)
        krr_model = model
        del X, Y, W_ref, pred
        gc.collect()

        # -- streaming KRR, two sweeps ----------------------------------
        N, D, S2, BR = (cfg[k] for k in
                        ("skrr_n", "skrr_d", "skrr_s", "skrr_block"))
        X0 = _normal(jax.random.PRNGKey(seed + 22), (BR, D), bf16)

        def block_fn(start, rows, X0):
            # A content-varying resident panel stands in for IO (the
            # bench's north-star row does the same).
            return jnp.roll(X0, start // rows, axis=0)

        y = jax.jit(jnp.sign)(
            jax.random.normal(jax.random.PRNGKey(seed + 23), (N,), f32))
        lam = 0.1
        model = ml.streaming_kernel_ridge(
            ml.GaussianKernel(D, sigma=8.0), block_fn, (N, D), y, lam, S2,
            SketchContext(seed=seed + 24),
            ml.KrrParams(max_split=0, iter_lim=2, tolerance=0.0),
            block_rows=BR, feature_dtype=bf16, block_args=(X0,),
        )
        ph.fields["streaming_krr"] = (
            f"{N}x{D}->{S2} bf16, 2 sweeps: the north-star program at a "
            "tenth of its 10M rows"
        )
        ph.require("streaming_krr_finite",
                   bool(jnp.all(jnp.isfinite(model.W))))
        # Reference: Gram and moment accumulated panel by panel in plain
        # f32 from the model's own feature map, one ridge solve; compared
        # on the first panel.  Features are bf16 in the trainer: 2^-5 of
        # the prediction scale, as above.
        features = jax.jit(lambda Xp: model.features(Xp))

        @jax.jit
        def fold(G, c, p, X0, y):
            Zp = model.features(block_fn(p * BR, BR, X0)).astype(f32)
            yp = jax.lax.dynamic_slice_in_dim(y, p * BR, BR)[:, None]
            return (G + jnp.matmul(Zp.T, Zp, precision="highest"),
                    c + jnp.matmul(Zp.T, yp, precision="highest"))

        G = jnp.zeros((S2, S2), f32)
        c = jnp.zeros((S2, 1), f32)
        for p in range(N // BR):
            G, c = fold(G, c, p, X0, y)
        W_ref = jax.jit(lambda G, c: jax.scipy.linalg.cho_solve(
            jax.scipy.linalg.cho_factor(
                G + lam * jnp.eye(S2, dtype=f32), lower=True), c))(G, c)
        Z0 = features(X0)  # block_fn(0, ...) is X0 itself
        ph.check("streaming_krr_predict_vs_plain_ridge",
                 _rel_err(_mm(Z0, model.W), _mm(Z0, W_ref)), 2.0 ** -5)
        del X0, y, G, c, W_ref, Z0, model
        gc.collect()

        # -- BlockADMM, three iterations ---------------------------------
        m, d, s = (cfg[k] for k in ("admm_m", "admm_d", "admm_s"))
        kx, ky = jax.random.split(jax.random.PRNGKey(seed + 25))
        X = _normal(kx, (m, d), f32)
        w = jax.random.normal(ky, (d,), f32)
        yc = jax.jit(lambda X, w: jnp.where(X @ w > 0, 1.0, -1.0))(X, w)
        kernel = ml.GaussianKernel(d, sigma=2.0)
        ctx = SketchContext(seed=seed + 26)
        maps = [kernel.create_rft(s, "regular", ctx) for _ in range(2)]
        model = ml.BlockADMMSolver(
            "hinge", "l2", maps, ml.ADMMParams(maxiter=3, data_partitions=4),
        ).train(X, yc)
        hist = [float(h) for h in model.history]
        ph.fields["admm"] = f"{m}x{d}->2x{s} hinge+l2 P=4, 3 iterations"
        ph.fields["admm_objective"] = hist
        ph.require("admm_objective_finite", bool(np.all(np.isfinite(hist))))
        # ADMM is not a descent method — the objective of the iterates
        # may rise a little before consensus pulls it down — so the
        # check is on the ends, not on every step.
        ph.require("admm_objective_decreased", hist[-1] <= hist[0])
        del X, yc, model, maps
        gc.collect()

        # -- the KRR CLI, in-process, on a seed-written hdf5_dense file --
        m, d, s = (cfg[k] for k in ("cli_m", "cli_d", "cli_s"))
        rng = np.random.default_rng(seed + 27)
        Xh = rng.standard_normal((m, d), dtype=np.float32)
        yh = (Xh @ rng.standard_normal(d).astype(np.float32)
              / np.sqrt(d)).astype(np.float32)
        with tempfile.TemporaryDirectory() as tmp:
            train = os.path.join(tmp, "train.h5")
            modelfile = os.path.join(tmp, "model.json")
            write_hdf5(train, Xh, yh)
            with contextlib.redirect_stdout(sys.stderr):
                rc = cli_krr.main([
                    "--trainfile", train, "--fileformat", "hdf5_dense",
                    "--modelfile", modelfile, "-a", "2", "--regression",
                    "--numfeatures", str(s), "--sigma", "8.0",
                    "--lambda", "0.1", "--seed", str(seed + 28),
                ])
            ph.require("cli_krr_rc0", rc == 0)
            cli_model = ml.load_model(modelfile)
        ph.fields["cli_krr"] = f"hdf5_dense {m}x{d}->{s} f32"
        Xj = jnp.asarray(Xh)
        features = jax.jit(lambda X: cli_model.features(X))
        W_ref = _plain_ridge(features(Xj), jnp.asarray(yh)[:, None], 0.1)
        # f32 model; the prediction matmul runs at the TPU's default
        # precision, which rounds both operands to bf16 (2^-9 each on
        # every term): 2^-5 of the prediction scale, as above.
        ph.check("cli_krr_predict_vs_plain_ridge",
                 _rel_err(jax.jit(lambda X: cli_model.predict(X))(Xj[:4096]),
                          _mm(features(Xj[:4096]), W_ref)),
                 2.0 ** -5)
    return krr_model


# ---------------------------------------------------------------------------
# phase 4: serve
# ---------------------------------------------------------------------------


def serve_requests(srv, A, b, krr_model, seed, counter, ph, n_each=16):
    """16 ls_solve + 16 predict through ``serve.Client``, half of each
    submitted together so they coalesce; every answer against the direct
    call; no compile and no error envelope after ``prime()``."""

    from libskylark_tpu import plans, serve
    from libskylark_tpu.serve.protocol import make_request

    system = srv.registry.get_system("sys")
    d = krr_model.input_dim
    rng = np.random.default_rng(seed + 30)
    noise = rng.standard_normal((n_each, system.m)).astype(np.float32)
    bs = np.asarray(b)[None, :] + 0.01 * noise
    xs = rng.standard_normal((n_each, 1, d)).astype(np.float32)

    # The direct calls, made BEFORE the counted window (they compile),
    # each on the whole set at once: a one-row matmul does not ride the
    # MXU, whose default precision rounds operands to bf16, so a direct
    # call row by row would differ from any batched one by that rounding.
    Qt, R = system.Qt, system.R
    SB = plans.apply(system.S, jnp.asarray(bs.T), "columnwise")
    direct_ls = np.asarray(jax.jit(
        lambda R, Qt, SB: jax.scipy.linalg.solve_triangular(
            R, Qt @ SB, lower=False))(R, Qt, SB)).T  # (n_each, n)
    direct_pred = np.asarray(
        krr_model.predict(jnp.asarray(xs.reshape(n_each, d))), np.float32
    ).reshape(n_each, -1)

    client = serve.Client(srv)
    c0, p0 = counter.snapshot(), plans.stats()
    half = n_each // 2
    got_ls, got_pred = [None] * n_each, [None] * n_each
    for i in range(half):  # one at a time
        got_ls[i] = client.ls_solve("sys", bs[i], check=True)
        got_pred[i] = client.predict("krr", xs[i], check=True)
    futs = [
        srv.submit(make_request("ls_solve", system="sys", b=bs[i]))
        for i in range(half, n_each)
    ] + [
        srv.submit(make_request("predict", model="krr", x=xs[i]))
        for i in range(half, n_each)
    ]
    envs = [f.result(timeout=600) for f in futs]
    c1, p1 = counter.snapshot(), plans.stats()
    ph.require("no_error_envelope", all(e.get("ok") for e in envs))
    ph.fields["coalesced_batches"] = sorted({
        e["trace"].get("batch_size", 0) for e in envs
    })
    ph.require("coalesced", any(e["trace"].get("coalesced") for e in envs))
    for j, i in enumerate(range(half, n_each)):
        got_ls[i] = envs[j]["result"]
        got_pred[i] = envs[half + j]["result"]

    def val(r, key):
        return np.asarray(r[key] if isinstance(r, dict) else r, np.float32)

    # Served and direct run the same sketch and the same QR solve; a
    # coalesced batch is a wider matmul (another tiling, another f32
    # summation order, bf16 passes on the TPU): 2^-7 of the scale.
    # Errors over the whole set against the set's scale: one prediction
    # is a single number, and may be near zero.
    served_ls = np.stack([val(got_ls[i], "x") for i in range(n_each)])
    served_pred = np.stack(
        [val(got_pred[i], "y").reshape(-1) for i in range(n_each)])
    ph.check("ls_solve_vs_direct", _rel_err(served_ls, direct_ls), 2.0 ** -7)
    ph.check("predict_vs_direct", _rel_err(served_pred, direct_pred),
             2.0 ** -5)
    ph.check("compile_requests_after_prime", c1[0] - c0[0], 0, error=False)
    ph.check("plan_compiles_after_prime", p1["compiles"] - p0["compiles"], 0,
             error=False)
    stats = srv.stats()
    counters = stats.get("counters", {})
    ph.fields["serve_counters"] = {
        k: v for k, v in counters.items() if isinstance(v, (int, float))
    }
    ph.check("error_envelopes", sum(
        v for k, v in counters.items()
        if "error" in k and isinstance(v, (int, float))
    ), 0, error=False)


def phase_serve(cfg, seed, counter, A, b, krr_model, workers=1):
    from libskylark_tpu import serve

    with Phase("serve", counter, workers=workers) as ph:
        srv = serve.Server(serve.ServeParams(workers=workers), seed=seed + 31)
        srv.register_system("sys", A)
        srv.register_model("krr", krr_model)
        t0 = time.perf_counter()
        srv.start()  # warm start + prime()
        ph.fields["prime_seconds"] = round(time.perf_counter() - t0, 3)
        ph.fields["primed"] = list(srv.primed)
        try:
            serve_requests(srv, A, b, krr_model, seed, counter, ph)
        finally:
            srv.stop()
    return srv


# ---------------------------------------------------------------------------
# --chips 4
# ---------------------------------------------------------------------------


def _device_stats(devs, key):
    return [(d.memory_stats() or {}).get(key) for d in devs]


def _holds_share(x, devs, ph, name):
    """Every device holds one addressable shard of ``x``, equal sizes."""
    shards = x.addressable_shards
    on = {s.device for s in shards}
    sizes = {s.data.size for s in shards}
    ph.require(f"{name}_on_all_devices", on == set(devs))
    ph.require(f"{name}_equal_shards",
               len(sizes) == 1 and sizes.pop() * len(devs) == x.size)


def phase_multichip(cfg, seed, counter, chips):

    from libskylark_tpu import SketchContext, linalg, ml, parallel
    from libskylark_tpu.sketch import CWT, JLT

    devs = jax.devices()[:chips]
    f32 = jnp.float32
    with Phase("multichip", counter, chips=chips) as ph:
        mesh = parallel.default_mesh(chips)
        ph.fields["mesh"] = dict(mesh.shape)
        m, n, s = cfg["sk_m"], cfg["sk_n"], cfg["sk_s"]
        A = _normal(jax.random.PRNGKey(seed + 40), (m, n), f32)
        As = parallel.shard_rows(A, mesh)
        _holds_share(As, devs, ph, "sketch_input")

        # Columnwise sketches of a row-sharded A: each device sketches
        # its rows, a psum merges.  Against the same call unsharded:
        # another summation order (per-device partials, then the psum).
        for name, S in (
            ("cwt", CWT(m, s, SketchContext(seed=seed + 41))),
            ("jlt", JLT(m, s, SketchContext(seed=seed + 42))),
        ):
            one = S.apply(A, "columnwise")
            fn = jax.jit(lambda X, S=S: S.apply(X, "columnwise"))
            sharded = fn(As)
            text = fn.lower(As).compile().as_text()
            ph.fields[f"{name}_collectives"] = sorted(
                op for op in ("all-reduce", "all-gather", "reduce-scatter",
                              "all-to-all", "collective-permute")
                if op in text
            )
            # f32 sums in another order; JLT's matmul runs bf16 passes
            # at default precision on both sides: 2^-7 of the scale.
            ph.check(f"{name}_sharded_vs_one_chip", _rel_err(sharded, one),
                     1e-5 if name == "cwt" else 2.0 ** -7)
            del one, sharded
        del A, As
        gc.collect()

        # Sharded least squares and SVD against the same calls on one
        # device.  Same sketch (same seed), so the answers agree up to
        # the summation order of the sharded reductions, carried through
        # QR/SVD and matmuls that run bf16 passes at the TPU's default
        # precision: 2^-7 of the scale.
        A, b = make_system(cfg, seed)
        As, bs = parallel.shard_rows(A, mesh), parallel.shard_rows(b, mesh)
        _holds_share(As, devs, ph, "system")
        x1 = linalg.approximate_least_squares(
            A, b, SketchContext(seed=seed + 43))
        x4 = jax.jit(lambda A, b: linalg.approximate_least_squares(
            A, b, SketchContext(seed=seed + 43)))(As, bs)
        ph.check("least_squares_sharded_vs_one_chip", _rel_err(x4, x1),
                 2.0 ** -7)
        k = cfg["svd_rank"]
        _, s1, _ = linalg.approximate_svd(A, k, SketchContext(seed=seed + 44))
        _, s4, _ = jax.jit(lambda A: linalg.approximate_svd(
            A, k, SketchContext(seed=seed + 44)))(As)
        ph.check("svd_values_sharded_vs_one_chip", _rel_err(s4, s1),
                 2.0 ** -7)
        del As, bs
        gc.collect()

        # BlockADMM with the data partitions over the mesh, three
        # iterations, against the same train on one device.
        m, d, s = (cfg[k_] for k_ in ("admm_m", "admm_d", "admm_s"))
        kx, ky = jax.random.split(jax.random.PRNGKey(seed + 45))
        X = _normal(kx, (m, d), f32)
        yc = np.asarray(jax.jit(lambda X, w: jnp.where(X @ w > 0, 1.0, -1.0))(
            X, jax.random.normal(ky, (d,), f32)), np.float32)
        kernel = ml.GaussianKernel(d, sigma=2.0)

        def train(Xin):
            ctx = SketchContext(seed=seed + 46)
            maps = [kernel.create_rft(s, "regular", ctx) for _ in range(2)]
            return ml.BlockADMMSolver(
                "hinge", "l2", maps,
                ml.ADMMParams(maxiter=3, data_partitions=chips),
            ).train(Xin, yc)

        Xs = parallel.shard(X, mesh, (parallel.ROWS, parallel.COLS))
        _holds_share(Xs, devs, ph, "admm_input")
        m1, m4 = train(X), train(Xs)
        ph.fields["admm_objective"] = [float(h) for h in m4.history]
        # Same maps, same iterations; the consensus sums reduce in
        # another order and the feature matmuls run bf16 passes.
        ph.check("admm_W_sharded_vs_one_chip", _rel_err(m4.W, m1.W), 2.0 ** -7)
        ph.check("admm_objective_sharded_vs_one_chip",
                 abs(m4.history[-1] - m1.history[-1])
                 / max(abs(m1.history[-1]), 1e-30), 1e-3)
        del X, Xs, m1, m4
        gc.collect()

    return A, b


def phase_multichip_serve(cfg, seed, counter, chips, A, b):
    """Serving over ``chips`` workers, each pinned to its own device,
    the phase-4 requests; then every device must have held data."""

    from libskylark_tpu import SketchContext, ml

    devs = jax.devices()[:chips]
    f32 = jnp.float32
    kx, ky = jax.random.split(jax.random.PRNGKey(seed + 20))
    mk, dk = cfg["krr_m"] // 8, cfg["krr_d"]
    krr_model = ml.approximate_kernel_ridge(
        ml.GaussianKernel(dk, sigma=4.0),
        _normal(kx, (mk, dk), jnp.bfloat16),
        jax.random.normal(ky, (mk, 1), f32), 0.1, cfg["krr_s"],
        SketchContext(seed=seed + 21),
    )
    allocs0 = _device_stats(devs, "num_allocs")
    phase_serve(cfg, seed, counter, A, b, krr_model, workers=chips)
    with Phase("multichip_placement", counter, chips=chips) as ph:
        # Worker i is pinned to device i and prime() runs every rung on
        # every pinned device, so serving must have allocated on each.
        allocs1 = _device_stats(devs, "num_allocs")
        peaks = _device_stats(devs, "peak_bytes_in_use")
        ph.fields["num_allocs_during_serve"] = [
            None if a0 is None or a1 is None else a1 - a0
            for a0, a1 in zip(allocs0, allocs1)
        ]
        ph.fields["peak_bytes_in_use_per_device"] = peaks
        if all(p is not None for p in peaks):
            ph.require("every_device_held_data", all(p > 0 for p in peaks))
        if all(a is not None for a in allocs0 + allocs1):
            ph.require("serve_allocated_on_every_device",
                       all(a1 > a0 for a0, a1 in zip(allocs0, allocs1)))
        if any(p is None for p in peaks):
            ph.fields["note"] = "backend reports no memory_stats"


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def run(args, cfg, counter) -> None:
    if args.chips > 1:
        A, b = phase_multichip(cfg, args.seed, counter, args.chips)
        phase_multichip_serve(cfg, args.seed, counter, args.chips, A, b)
        return
    phase_sketch(cfg, args.seed, counter, args.rehearse)
    A, b = phase_solve(cfg, args.seed, counter)
    krr_model = phase_train(cfg, args.seed, counter)
    phase_serve(cfg, args.seed, counter, A, b, krr_model)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4: the sharded paths and their one-chip "
                        "comparisons only")
    p.add_argument("--rehearse", action="store_true",
                   help="tiny sizes, interpret-mode kernels, any backend "
                        "(control-flow rehearsal; not a chip run)")
    args = p.parse_args(argv)

    if args.rehearse:
        for k in ("SKYLARK_PALLAS_WINDOW", "SKYLARK_PALLAS_GATHER"):
            os.environ.setdefault(k, "interpret")

    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse and dev.platform != "tpu":
        print(f"chip_smoke: no TPU (first device is {dev.platform!r}); "
              "this is a chip run or nothing", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from libskylark_tpu.utils import compile_cache

    cache_dir = compile_cache.place()
    counter = CompileCounter()
    cfg = TINY if args.rehearse else REAL
    emit({"phase": "start", "seed": args.seed, "chips": args.chips,
          "jax": jax.__version__, "x64": bool(jax.config.jax_enable_x64),
          "compile_cache_dir": cache_dir,
          "compile_cache_entries": len(os.listdir(cache_dir))
          if os.path.isdir(cache_dir) else 0})
    t0 = time.perf_counter()
    run(args, cfg, counter)
    emit({"phase": "total", "seconds": round(time.perf_counter() - t0, 3),
          "compile_requests": counter.requests, "cache_hits": counter.hits,
          "compile_seconds": round(counter.seconds, 3)})
    last = {"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}
    if args.rehearse:
        last["rehearsal"] = True
    emit(last)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
