"""Headline benchmarks, one JSON line per config.

Requires a TPU (``SKYLARK_BENCH_SMOKE=1`` alone runs anywhere, at toy
sizes, to exercise the artifact path); any failed row makes the exit
code non-zero.  Emits one JSON line
``{"metric", "value", "unit", "vs_baseline"}`` per headline config; the
LAST line is the headline metric (JLT dense sketch-apply TFLOP/s) and
carries the full table again under ``"submetrics"`` so a driver that
parses only the final line still records everything.

``vs_baseline`` semantics per line:
- the JLT headline reports measured TFLOP/s over the chip's bf16 peak
  (MFU) — the reference publishes no numbers to beat
  (docs/performance.md);
- every other line reports ``recorded / measured`` for times against
  the constants in this file (builder-recorded, pre-PR 21; the benchmark
  PR replaces them).

Budget discipline (round 4 — the round-3 driver capture died rc=124 with
the headline scheduled last, losing the most important rows): the two
flagship configs (JLT headline, north-star streaming KRR) run FIRST,
secondaries follow in descending importance, and a global wall-clock
budget (``SKYLARK_BENCH_BUDGET_S``, default 1500 s — deliberately under
any plausible driver timeout) governs the rest: pooling stops extending
when the deadline nears, configs that cannot fit emit an explicit
``"skipped: budget"`` row instead of dying mid-list, and a SIGTERM from
an outer timeout still flushes the final headline+submetrics line.

Timing notes: all timings end in a scalar readback (which waits for the
device, as ``block_until_ready`` does); R independent applies (each with
a distinct counter block, so XLA cannot CSE them) run inside ONE jitted
call, and the fixed dispatch + readback cost is cancelled by
differencing two rep counts, pooling minima over many interleaved rounds
(min-plus-noise: the unbiased move is one difference of pooled minima).
The timing method is left as it was for the benchmark PR to settle.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from libskylark_tpu.core.context import SketchContext

_T0 = time.monotonic()
_BUDGET_S = float(os.environ.get("SKYLARK_BENCH_BUDGET_S", "1500"))

# Smoke mode (``SKYLARK_BENCH_SMOKE=1``): tiny dims and minimal pooling,
# so the WHOLE artifact path — headline, rows, final line — runs in
# seconds on any backend.  The numbers are meaningless; the contract
# (valid JSON rows) is what it exercises.  Every other run needs a TPU.
_SMOKE = os.environ.get("SKYLARK_BENCH_SMOKE") == "1"

# Config filter (``SKYLARK_BENCH_ONLY=<substring>``): non-headline
# configs whose name does not contain the substring emit an explicit
# ``skipped: filter`` row instead of running.  The headline always runs
# — the final-line artifact contract does not bend to the filter.
_ONLY = os.environ.get("SKYLARK_BENCH_ONLY") or None


def _selected(name: str) -> bool:
    return _ONLY is None or _ONLY in name


def _remaining() -> float:
    """Seconds left in the global bench budget."""
    return _BUDGET_S - (time.monotonic() - _T0)


# Peak dense bf16 TFLOP/s per chip, keyed by ``device_kind`` as JAX
# reports it.  Source: Google Cloud documentation, "TPU v5e" (197
# TFLOP/s bf16 per chip).  A kind this table does not hold is an error,
# not a default.
_PEAK_BF16_TFLOPS = {
    "TPU v5 lite": 197.0,
}


def _peak_tflops(device) -> float:
    kind = device.device_kind
    if kind not in _PEAK_BF16_TFLOPS:
        raise KeyError(
            f"no peak TFLOP/s on record for device kind {kind!r}; add it "
            "to _PEAK_BF16_TFLOPS with its source"
        )
    return _PEAK_BF16_TFLOPS[kind]


def _timed(fn, *args) -> float:
    t0 = time.perf_counter()
    np.asarray(fn(*args))  # the readback waits for the device
    return time.perf_counter() - t0


_LAST_CONTENTION: float | None = None


def _rep_diff(build, A, r1=4, r2=16, rounds=25, max_bursts=4) -> float:
    """Seconds per single apply, by differencing two rep counts.

    ``build(k)`` must return a jitted callable running k independent
    applies of the op under test, reduced to a scalar.

    Contention-adaptive pooling (round 3): minima are pooled per burst
    with pauses in between; if the burst-to-burst spread of the derived
    marginal stays ≤5% after two bursts the measurement is accepted,
    otherwise pooling extends (up to ``max_bursts``) to give transient
    host contention more chances to clear — min-plus-noise
    justifies the final min across all bursts.  The residual spread is
    recorded in ``_LAST_CONTENTION`` and emitted with the metric, so a
    low driver capture is self-explaining (VERDICT r2 item 5).
    """
    global _LAST_CONTENTION
    _LAST_CONTENTION = None  # a failed config must not inherit a stale value
    if _SMOKE:
        # one burst, few rounds, small rep spread: enough that t2 > t1
        # holds on a quiet CPU, cheap enough for a subprocess test
        r1, r2, rounds, max_bursts = 2, 8, 3, 1
    args = A if isinstance(A, tuple) else (A,)
    f1, f2 = build(r1), build(r2)
    _timed(f1, *args), _timed(f2, *args)  # compile both
    t1s, t2s, per_burst = [], [], []
    for burst in range(max_bursts):
        if burst:
            # Budget-aware pooling (round 4): extending into another
            # burst is insurance against transient contention — worth
            # nothing if it pushes later configs past the deadline.
            if _remaining() < 60:
                break
            time.sleep(10)
        b1, b2 = [], []
        for i in range(rounds):
            b1.append(_timed(f1, *args))
            b2.append(_timed(f2, *args))
            # Keep pairs balanced: break between rounds only, and only
            # after enough rounds that a min is meaningful.
            if i >= 3 and _remaining() < 30:
                break
        t1s += b1
        t2s += b2
        if min(b2) > min(b1):
            per_burst.append((min(b2) - min(b1)) / (r2 - r1))
        if burst >= 1 and len(per_burst) >= 2:
            spread = (max(per_burst) - min(per_burst)) / min(per_burst)
            if spread <= 0.05:
                break
        if _remaining() < 60:
            break
    t1, t2 = min(t1s), min(t2s)
    if t2 <= t1:
        raise RuntimeError(
            f"benchmark timing inconsistent (t1={t1:.4f}s >= t2={t2:.4f}s); "
            "rerun on a quieter machine"
        )
    _LAST_CONTENTION = (
        round((max(per_burst) - min(per_burst)) / min(per_burst), 4)
        if len(per_burst) >= 2
        # Fewer than two bursts yielded a usable marginal: contention so
        # heavy the spread is unmeasurable — flag with -1 rather than
        # omitting the field (absent = custom-timing config, never
        # "noisy"; BASELINE.md round-3 integrity note).
        else -1.0
    )
    return (t2 - t1) / (r2 - r1)


def _emit(metric, value, unit, vs_baseline, table, contention="auto"):
    row = {
        "metric": metric,
        "value": round(float(value), 4),
        "unit": unit,
        "vs_baseline": round(float(vs_baseline), 4),
    }
    # Every row names the backend it ran on.
    row["backend"] = str(jax.default_backend())
    if contention == "auto":
        contention = _LAST_CONTENTION
    if contention is not None:
        # burst-to-burst spread of the marginal: ≤0.05 = quiet machine;
        # larger values flag host contention the pooling could
        # not fully clear (the value is then a lower-confidence upper
        # bound on the true time).
        row["contention"] = contention
    table.append(row)
    print(json.dumps(row), flush=True)


# ---------------------------------------------------------------------------
# configs
# ---------------------------------------------------------------------------


def bench_jlt(on_tpu, table):
    """Headline: fused counter-generated Omega + bf16 MXU matmul."""
    from libskylark_tpu.sketch.dense import JLT

    if on_tpu:
        m, n, s, dtype = 262_144, 4096, 1024, jnp.bfloat16
    elif _SMOKE:
        m, n, s, dtype = 8_192, 512, 128, jnp.float32
    else:
        m, n, s, dtype = 16_384, 1024, 256, jnp.float32

    def build(reps):
        ctx = SketchContext(seed=92)
        sketches = [JLT(n, s, ctx) for _ in range(reps)]

        def run(A):
            acc = jnp.zeros((), jnp.float32)
            for S in sketches:
                # abs is NONLINEAR: it blocks XLA's reduce(dot) algebraic rewrite
                # (sum(A@B) -> (1ᵀA)(B·1)), which would gut the measurement
                acc += jnp.sum(jnp.abs(S.apply(A, "rowwise").astype(jnp.float32)))
            return acc

        return jax.jit(run)

    A = jax.random.normal(jax.random.PRNGKey(0), (m, n), dtype=dtype)
    per = _rep_diff(build, A)
    tflops = 2.0 * m * n * s / per / 1e12
    return tflops, per


def bench_fjlt(on_tpu, dtype, baseline_ms, table):
    from libskylark_tpu.sketch.fjlt import FJLT

    if on_tpu:
        m, n, s = 131_072, 4096, 1024
    else:
        m, n, s = 4096, 1024, 256

    def build(reps):
        ctx = SketchContext(seed=17)
        sketches = [FJLT(n, s, ctx) for _ in range(reps)]

        def run(A):
            acc = jnp.zeros((), jnp.float32)
            for S in sketches:
                # abs is NONLINEAR: it blocks XLA's reduce(dot) algebraic rewrite
                # (sum(A@B) -> (1ᵀA)(B·1)), which would gut the measurement
                acc += jnp.sum(jnp.abs(S.apply(A, "rowwise").astype(jnp.float32)))
            return acc

        return jax.jit(run)

    A = jax.random.normal(jax.random.PRNGKey(1), (m, n), dtype=dtype)
    per = _rep_diff(build, A, r1=4, r2=16, rounds=20)
    name = "bf16" if dtype == jnp.bfloat16 else "f32"
    _emit(
        f"FJLT {m}x{n}->{s} {name} apply",
        per * 1e3,
        "ms",
        baseline_ms / (per * 1e3) if on_tpu else 1.0,
        table,
    )


def bench_cwt(on_tpu, table):
    from libskylark_tpu.sketch.hash import CWT

    if on_tpu:
        m, n, s = 131_072, 4096, 1024
    else:
        m, n, s = 8192, 512, 128

    def build(reps):
        ctx = SketchContext(seed=29)
        sketches = [CWT(m, s, ctx) for _ in range(reps)]

        def run(A):
            acc = jnp.zeros((), jnp.float32)
            for S in sketches:
                acc += jnp.sum(jnp.abs(S.apply(A, "columnwise").astype(jnp.float32)))
            return acc

        return jax.jit(run)

    A = jax.random.normal(jax.random.PRNGKey(2), (m, n), jnp.float32)
    per = _rep_diff(build, A, r1=4, r2=12, rounds=20)
    _emit(
        f"CWT {m}x{n}->{s} dense columnwise apply",
        per * 1e3,
        "ms",
        19.8 / (per * 1e3) if on_tpu else 1.0,
        table,
    )


def bench_frft(on_tpu, dtype, baseline_ms, table):
    """Fastfood via the realized-W MXU path (sketch/frft.py round 3)."""
    from libskylark_tpu.sketch.frft import FastGaussianRFT

    if on_tpu:
        m, n, s = 131_072, 4096, 2048
    else:
        m, n, s = 4096, 256, 512

    def build(reps):
        ctx = SketchContext(seed=37)
        sketches = [FastGaussianRFT(n, s, ctx, sigma=2.0) for _ in range(reps)]

        def run(A):
            acc = jnp.zeros((), jnp.float32)
            for S in sketches:
                acc += jnp.sum(jnp.abs(S.apply(A, "rowwise").astype(jnp.float32)))
            return acc

        return jax.jit(run)

    A = jax.random.normal(jax.random.PRNGKey(5), (m, n), dtype=dtype)
    per = _rep_diff(build, A, r1=2, r2=8, rounds=15)
    name = "bf16" if dtype == jnp.bfloat16 else "f32"
    _emit(
        f"FastGaussianRFT {m}x{n}->{s} {name} apply",
        per * 1e3,
        "ms",
        baseline_ms / (per * 1e3) if on_tpu else 1.0,
        table,
    )


def bench_ppt(on_tpu, dtype, baseline_ms, table):
    """TensorSketch q=3 (bf16 = matmul-DFT path, f32 = complex FFT)."""
    from libskylark_tpu.sketch.ppt import PPT

    if on_tpu:
        m, n, s = 131_072, 4096, 1024
    else:
        m, n, s = 4096, 256, 128

    def build(reps):
        ctx = SketchContext(seed=43)
        sketches = [PPT(n, s, ctx, q=3) for _ in range(reps)]

        def run(A):
            acc = jnp.zeros((), jnp.float32)
            for S in sketches:
                acc += jnp.sum(jnp.abs(S.apply(A, "rowwise").astype(jnp.float32)))
            return acc

        return jax.jit(run)

    A = jax.random.normal(jax.random.PRNGKey(6), (m, n), dtype=dtype)
    # r2 capped at 2: three concurrent f32-FFT rep bodies overflow HBM
    # (XLA schedules their ~0.5 GB FFT temps together).
    per = _rep_diff(build, A, r1=1, r2=2, rounds=12)
    name = "bf16" if dtype == jnp.bfloat16 else "f32"
    _emit(
        f"PPT {m}x{n}->{s} q=3 {name} apply",
        per * 1e3,
        "ms",
        baseline_ms / (per * 1e3) if on_tpu else 1.0,
        table,
    )


def bench_mmt(on_tpu, table):
    """Non-sign hash sketch (Cauchy values) — the scaled-one-hot f32
    path must stay at CWT speed (hash.py round 3)."""
    from libskylark_tpu.sketch.hash import MMT

    if on_tpu:
        m, n, s = 131_072, 4096, 1024
    else:
        m, n, s = 8192, 512, 128

    def build(reps):
        ctx = SketchContext(seed=47)
        sketches = [MMT(m, s, ctx) for _ in range(reps)]

        def run(A):
            acc = jnp.zeros((), jnp.float32)
            for S in sketches:
                acc += jnp.sum(jnp.abs(S.apply(A, "columnwise").astype(jnp.float32)))
            return acc

        return jax.jit(run)

    A = jax.random.normal(jax.random.PRNGKey(7), (m, n), jnp.float32)
    per = _rep_diff(build, A, r1=4, r2=12, rounds=15)
    _emit(
        f"MMT {m}x{n}->{s} dense f32 columnwise apply",
        per * 1e3,
        "ms",
        18.1 / (per * 1e3) if on_tpu else 1.0,
        table,
    )


def bench_stream_chunk(on_tpu, table):
    """Fused stream-chunk throughput (round-8 tentpole): one streaming
    columnwise pass driven through ``plans.accumulate_slice``, with the
    per-chunk sketch-apply + accumulator-add traced as a SINGLE planned
    executable (``fused=True``, the hash sketches' window-kernel emit
    folds the add on TPU).  Emitted value is end-to-end Mrows/s over the
    whole pass; ``vs_baseline`` is the fused/unfused speedup on the same
    chunks — the two paths are bitwise identical by the
    ``apply_slice_kernel_acc`` contract, so the ratio isolates launch
    and fusion overhead.  First capture: no recorded baseline row."""
    from libskylark_tpu import plans
    from libskylark_tpu.sketch.hash import CWT, MMT

    if on_tpu:
        chunk, n, s, nchunks = 65_536, 2048, 1024, 8
    else:
        chunk, n, s, nchunks = 4096, 256, 128, 4
    m = chunk * nchunks
    X = jax.random.normal(jax.random.PRNGKey(21), (chunk, n), jnp.float32)

    for name, mk in (("CWT", CWT), ("MMT", MMT)):
        S = mk(m, s, SketchContext(seed=61))
        S.hoistable_operands(jnp.float32)  # realize outside the timings

        def run(fused):
            acc = jnp.zeros((s, n), jnp.float32)
            for c in range(nchunks):
                acc = plans.accumulate_slice(
                    S, acc, X, c * chunk, true_rows=chunk, fused=fused
                )
            return jax.block_until_ready(acc)

        plans.clear()
        run(True), run(False)  # build both plan-cache entries
        t_fused = min(_timed(run, True) for _ in range(5))
        t_unfused = min(_timed(run, False) for _ in range(5))
        _emit(
            f"{name} fused stream-chunk columnwise "
            f"{nchunks}x{chunk}x{n}->{s}",
            (m / t_fused) / 1e6,
            "Mrows/s",
            t_unfused / t_fused,
            table,
            contention=None,  # min-of-5 custom loop — no burst spread
        )


def bench_overlap(on_tpu, table):
    """Async device-overlap streaming (round-11 tentpole): the same
    columnwise CWT pass folded twice — ``overlap=True`` (host syncs only
    at chunk boundaries; batch k+1's staging rides JAX async dispatch
    under batch k's compute) vs ``overlap=False`` (``block_until_ready``
    after every fold step, the serial anchor).  The two are bitwise
    identical by the overlap contract (same blocks, same order, same
    IEEE adds — only the host's wait points move), so ``vs_baseline``
    (serial/overlapped) isolates pure dispatch-overlap win.  A second
    row reports the overlap-efficiency submetric: the fraction of
    producer (parse + host→device staging) seconds hidden under compute,
    from the prefetch counters of one overlapped pass."""
    from libskylark_tpu import streaming, telemetry
    from libskylark_tpu.sketch.hash import CWT
    from libskylark_tpu.streaming import StreamParams

    if on_tpu:
        br, n, s, nb = 65_536, 2048, 1024, 8
    else:
        br, n, s, nb = 4096, 256, 128, 4
    m = br * nb
    rng = np.random.default_rng(33)
    host = [rng.standard_normal((br, n)).astype(np.float32) for _ in range(nb)]
    S = CWT(m, s, SketchContext(seed=71))
    S.hoistable_operands(jnp.float32)  # realize outside the timings

    def run(overlap):
        return jax.block_until_ready(
            streaming.sketch(
                lambda start: iter(host[start:]),
                S,
                ncols=n,
                params=StreamParams(overlap=overlap),
            )
        )

    run(True), run(False)  # compile the planned fold once
    t_over = min(_timed(run, True) for _ in range(5))
    t_serial = min(_timed(run, False) for _ in range(5))

    prev = os.environ.get("SKYLARK_TELEMETRY")
    os.environ["SKYLARK_TELEMETRY"] = "1"
    telemetry.reset()
    try:
        run(True)
        snap = telemetry.snapshot()
    finally:
        if prev is None:
            os.environ.pop("SKYLARK_TELEMETRY", None)
        else:
            os.environ["SKYLARK_TELEMETRY"] = prev
    eff = snap.get("overlap_efficiency")
    _emit(
        f"CWT overlapped stream columnwise {nb}x{br}x{n}->{s}",
        (m / t_over) / 1e6,
        "Mrows/s",
        t_serial / t_over,
        table,
        contention=None,  # min-of-5 custom loop — no burst spread
    )
    _emit(
        f"overlap efficiency (hidden transfer fraction, {nb}x{br}x{n})",
        eff if eff is not None else -1,
        "ratio",
        1.0,
        table,
        contention=None,  # counter ratio, not a timing
    )


def bench_qrft(on_tpu, table):
    """QMC random features (Halton + inverse-CDF epilogue on the dense
    engine) — closes the transform-family perf matrix (VERDICT r3 #9).
    First capture: no recorded baseline yet, vs_baseline fixed at 1.0
    (BASELINE.md records the value this emits)."""
    from libskylark_tpu.sketch.rft import GaussianQRFT

    if on_tpu:
        m, n, s = 131_072, 4096, 2048
    else:
        m, n, s = 4096, 256, 128

    def build(reps):
        ctx = SketchContext(seed=59)
        # QRFT consumes no counters — distinct skips keep reps CSE-proof.
        sketches = [
            GaussianQRFT(n, s, ctx, sigma=4.0, skip=1 + r * s)
            for r in range(reps)
        ]

        def run(A):
            acc = jnp.zeros((), jnp.float32)
            for S in sketches:
                acc += jnp.sum(jnp.abs(S.apply(A, "rowwise").astype(jnp.float32)))
            return acc

        return jax.jit(run)

    A = jax.random.normal(jax.random.PRNGKey(10), (m, n), jnp.float32)
    per = _rep_diff(build, A, r1=2, r2=6, rounds=12)
    _emit(
        f"GaussianQRFT {m}x{n}->{s} f32 apply",
        per * 1e3,
        "ms",
        1.0,
        table,
    )


def bench_rlt(on_tpu, table):
    """Random Laplace transform (Lévy dense engine + exp epilogue).
    First capture: vs_baseline fixed at 1.0 (see bench_qrft)."""
    from libskylark_tpu.sketch.rlt import ExpSemigroupRLT

    if on_tpu:
        m, n, s = 131_072, 4096, 1024
    else:
        m, n, s = 4096, 256, 128

    def build(reps):
        ctx = SketchContext(seed=61)
        sketches = [ExpSemigroupRLT(n, s, ctx, beta=1.0) for _ in range(reps)]

        def run(A):
            acc = jnp.zeros((), jnp.float32)
            for S in sketches:
                acc += jnp.sum(jnp.abs(S.apply(A, "rowwise").astype(jnp.float32)))
            return acc

        return jax.jit(run)

    # Semigroup-kernel features need non-negative inputs (histograms).
    A = jnp.abs(jax.random.normal(jax.random.PRNGKey(11), (m, n), jnp.float32))
    per = _rep_diff(build, A, r1=2, r2=6, rounds=12)
    _emit(
        f"ExpSemigroupRLT {m}x{n}->{s} f32 apply",
        per * 1e3,
        "ms",
        1.0,
        table,
    )


def bench_sparse_cwt(on_tpu, table):
    """Input-sparsity-time sketch: CWT on a 1e6x1e5 BCOO, 1e7 nnz,
    dense_output (sort-free segment_sum — hash.py round 3)."""
    from jax.experimental import sparse as jsparse

    from libskylark_tpu.sketch.hash import CWT

    if on_tpu:
        n, m, s, nnz = 1_000_000, 100_000, 1024, 10_000_000
    else:
        n, m, s, nnz = 10_000, 1_000, 128, 100_000
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(8), 3)
    rows = jax.random.randint(k1, (nnz,), 0, n, dtype=jnp.int32)
    cols = jax.random.randint(k2, (nnz,), 0, m, dtype=jnp.int32)
    data = jax.random.normal(k3, (nnz,), jnp.float32)
    idx = jnp.stack([rows, cols], axis=1)

    def build(reps):
        ctx = SketchContext(seed=53)
        sketches = [CWT(n, s, ctx) for _ in range(reps)]

        def run(data, idx):
            A = jsparse.BCOO((data, idx), shape=(n, m))
            acc = jnp.zeros((), jnp.float32)
            for S in sketches:
                out = S.apply(A, "columnwise", dense_output=True)
                acc += jnp.sum(jnp.abs(out))
            return acc

        return jax.jit(run)

    # The default route (XLA's scatter on every backend: the flat Pallas
    # kernel is refused by the chip's compiler and is on no default
    # route — hash._segment_sum).
    per = _rep_diff(build, (data, idx), r1=1, r2=3, rounds=8)
    _emit(
        f"CWT BCOO {n}x{m} nnz={nnz:.0e} -> {s} dense_output",
        per * 1e3,
        "ms",
        357.0 / (per * 1e3) if on_tpu else 1.0,
        table,
    )


def bench_streaming_krr(on_tpu, table):
    """North-star single-chip config: 10M×4096 → 2048-feature KRR, rows
    AND features streamed, bf16 (BASELINE.md North-star section).
    Steady s/sweep via the solver's PhaseTimer (sweep0 absorbs compiles;
    a content-varying resident panel stands in for IO — a loop-invariant
    panel would be LICM'd into a fictitious >100% MFU reading)."""
    from libskylark_tpu.ml import (
        GaussianKernel,
        KrrParams,
        streaming_kernel_ridge,
    )
    from libskylark_tpu.utils import PhaseTimer

    if on_tpu:
        N, D, S, BR, sweeps = 10_000_000, 4096, 2048, 125_000, 3
    else:
        N, D, S, BR, sweeps = 4096, 64, 128, 512, 2

    X0 = jax.random.normal(jax.random.PRNGKey(9), (BR, D), jnp.bfloat16)

    def block_fn(start, rows, X0):
        # Per-panel row ROTATION: not algebraically reducible, so no XLA
        # simplifier can commute it out of the dot and hoist the matmul
        # (a scalar multiple could be rewritten s*dot(X0, W); an additive
        # shift folds into colsum(W) — both re-open the LICM trap).
        return jnp.roll(X0, start // rows, axis=0)

    y = jnp.asarray(
        np.sign(np.random.default_rng(0).standard_normal(N)), jnp.float32
    )
    timer = PhaseTimer()
    streaming_kernel_ridge(
        GaussianKernel(D, sigma=8.0), block_fn, (N, D), y, 0.1, S,
        SketchContext(seed=72),
        KrrParams(max_split=0, iter_lim=sweeps, tolerance=0.0),
        block_rows=BR, feature_dtype=jnp.bfloat16, block_args=(X0,),
        timer=timer,
    )
    per = timer.totals["sweep"] / timer.counts["sweep"]
    _emit(
        f"streaming KRR {N}x{D}->{S} bf16 (north-star, hot panels)",
        per,
        "s/sweep",
        2.69 / per if on_tpu else 1.0,
        table,
        contention=None,  # PhaseTimer steady sweeps — no burst spread
    )


def bench_streaming_svd(on_tpu, table):
    """The BASELINE.json headline config: 1e7x1024, k=100 (bf16 panels)."""
    from libskylark_tpu.linalg import (
        SVDParams,
        streaming_approximate_svd,
        synthetic_lowrank_blocks,
    )

    if on_tpu:
        m, n, k, br, dtype = 10_000_000, 1024, 100, 250_000, jnp.bfloat16
    else:
        m, n, k, br, dtype = 20_000, 128, 10, 5_000, jnp.float32
    ctx = SketchContext(seed=5)
    blocks = synthetic_lowrank_blocks(ctx, m, n, k, noise=0.01, dtype=dtype)

    def run():
        _, s, V = streaming_approximate_svd(
            blocks, (m, n), k, SketchContext(seed=6),
            SVDParams(num_iterations=1), block_rows=br,
        )
        return jnp.sum(s)

    _timed(run)  # compile sweep programs
    dt = min(_timed(run) for _ in range(2 if on_tpu else 3))
    _emit(
        f"streaming randomized SVD {m}x{n} k={k}",
        dt,
        "s",
        21.0 / dt if on_tpu else 1.0,
        table,
        contention=None,  # single-shot timing — no burst spread measured
    )


def bench_ridge(on_tpu, table):
    """Random-feature ridge solve (feature map + Gram + solve)."""
    from libskylark_tpu.ml import GaussianKernel

    if on_tpu:
        m, d, s = 262_144, 4096, 2048
    else:
        m, d, s = 8192, 256, 128
    kernel = GaussianKernel(d, sigma=4.0)

    def build(reps):
        ctx = SketchContext(seed=31)
        maps = [kernel.create_rft(s, "regular", ctx) for _ in range(reps)]

        def run(X, Y):
            acc = jnp.zeros((), jnp.float32)
            for fm in maps:
                Z = fm.apply(X, "rowwise").astype(jnp.bfloat16)
                G = (Z.T @ Z).astype(jnp.float32) + 0.1 * jnp.eye(s)
                W = jnp.linalg.solve(G, (Z.T @ Y.astype(Z.dtype)).astype(jnp.float32))
                acc += jnp.sum(jnp.abs(W))
            return acc

        return jax.jit(run)

    X = jax.random.normal(jax.random.PRNGKey(3), (m, d), jnp.bfloat16)
    Y = jax.random.normal(jax.random.PRNGKey(4), (m, 1), jnp.float32)

    f1, f2 = build(1), build(3)
    _timed(f1, X, Y), _timed(f2, X, Y)
    t1s, t2s = [], []
    for _ in range(10):
        t1s.append(_timed(f1, X, Y))
        t2s.append(_timed(f2, X, Y))
    per = (min(t2s) - min(t1s)) / 2
    if per <= 0:
        per = min(t1s)  # degenerate timing; report the single-solve time
    _emit(
        f"random-feature ridge solve {m}x{d}->{s} feats (marginal)",
        per * 1e3,
        "ms",
        31.0 / (per * 1e3) if on_tpu else 1.0,
        table,
        contention=None,  # custom timing loop — no burst spread measured
    )


def bench_admm(on_tpu, table):
    from libskylark_tpu.ml import ADMMParams, BlockADMMSolver, GaussianKernel

    # Marginal s/iter via (t_201 - t_1)/200: the scan-fused iteration
    # costs ~12 ms on a v5e chip, far below the fixed setup+compile that
    # rides every train() call (fresh jitted closures per call), so the
    # iteration count must be large enough that the signal (~2.4 s)
    # dominates compile jitter.  The round-1 recorded 0.92 s/iter was
    # total/iters of a 10-iteration run — fixed-cost dominated, not a
    # steady-state number (reconciled in BASELINE.md).
    if on_tpu:
        m, d, s, iters = 262_144, 128, 2048, 201
    else:
        m, d, s, iters = 4096, 16, 64, 5
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
    y = jnp.asarray((rng.standard_normal(m) > 0) * 2.0 - 1.0, jnp.float32)
    kernel = GaussianKernel(d, sigma=2.0)
    ctx = SketchContext(seed=41)
    maps = [kernel.create_rft(s, "regular", ctx) for _ in range(2)]

    def run(n_iter):
        solver = BlockADMMSolver(
            "hinge", "l2", maps,
            ADMMParams(maxiter=n_iter, data_partitions=4),
        )
        model = solver.train(X, y)
        return jax.block_until_ready(model.W)

    # train() jits fresh closures per call, so every timed call includes
    # one trace+compile; the two programs (scan length 1 vs N) have near-
    # identical structure, so compile time CANCELS in the difference.
    # min over repeats suppresses compile jitter.
    for attempt in range(2):
        t1 = min(_timed(lambda _: run(1), None) for _ in range(2))
        tN = min(_timed(lambda _: run(iters), None) for _ in range(2))
        if tN > t1:
            break
        time.sleep(10)  # transient contention: let it clear, retry once
    if tN <= t1:
        raise RuntimeError(
            f"ADMM timing inconsistent (t1={t1:.2f}s >= tN={tN:.2f}s)"
        )
    per = (tN - t1) / (iters - 1)
    _emit(
        f"BlockADMM {m}x{d} -> 2x{s} feats hinge+l2 P=4",
        per,
        "s/iter",
        0.92 / per if on_tpu else 1.0,
        table,
        contention=None,  # custom timing loop — no burst spread measured
    )


def bench_train(on_tpu, table):
    """Distributed-training rows (docs/distributed_training.md): (a)
    end-to-end world=1 elastic BlockADMM training throughput (rows/s:
    stream + factor + iterate) vs the in-process
    ``BlockADMMSolver.train`` on the SAME data/maps/params —
    ``vs_baseline`` is distributed/in-process rows/s (the world=1 model
    is bitwise the in-process one, so the ratio prices the elastic
    plumbing alone); (b) kill-to-first-consensus resume latency: the
    training loop is preempted right after a committed ADMM chunk, the
    world restarts with ``resume=True``, and the value is wall-seconds
    from the kill to the FIRST post-resume train-chunk commit (a train
    chunk commits only after its final consensus merge) — the restore +
    re-stream + re-factor latency a preempted world pays before forward
    progress resumes; first capture, vs_baseline fixed at 1.0; (c)
    bf16-vs-f32 train step: marginal s/iter of the fused rank step at
    ``compute_dtype=bf16`` on identical streamed blocks, with
    ``vs_baseline`` the f32/bf16 per-iteration speedup."""
    import tempfile

    from libskylark_tpu.ml import (
        ADMMParams,
        BlockADMMSolver,
        GaussianKernel,
        prepare_rank_admm,
        rank_chunked_solver,
        stream_feature_blocks,
    )
    from libskylark_tpu.ml.distributed import DistributedBlockADMMTrainer
    from libskylark_tpu.resilient import FaultPlan, SimulatedPreemption
    from libskylark_tpu.streaming import ElasticParams, RowPartition

    if on_tpu:
        n, d, s, P, iters, br = 131_072, 64, 512, 8, 40, 8192
    else:
        n, d, s, P, iters, br = 4096, 16, 64, 4, 8, 512
    rng = np.random.default_rng(29)
    X = np.asarray(rng.standard_normal((n, d)), np.float32)
    y = np.asarray(rng.standard_normal(n), np.float32)
    ctx = SketchContext(seed=29)
    kernel = GaussianKernel(d, sigma=2.0)
    maps = [kernel.create_rft(s, "regular", ctx) for _ in range(2)]
    params = ADMMParams(rho=1.0, lam=0.01, maxiter=iters, data_partitions=P)
    part = RowPartition(nrows=n, batch_rows=br, world_size=1)

    def source(start):
        def gen():
            for b in range(start, part.num_batches):
                lo = b * br
                yield X[lo : lo + br], y[lo : lo + br]

        return gen()

    # (a) rows/s through the elastic trainer vs the in-process solver.
    # Both time one full train() including its per-call trace+compile —
    # the same contract either entry point gives a fresh caller.
    t0 = time.perf_counter()
    m_ref = BlockADMMSolver("squared", "l2", maps, params).train(
        jnp.asarray(X), jnp.asarray(y), regression=True
    )
    jax.block_until_ready(m_ref.W)
    t_base = time.perf_counter() - t0
    t0 = time.perf_counter()
    m_dist, _ = DistributedBlockADMMTrainer(
        "squared", "l2", maps, params, ElasticParams(prefetch=0)
    ).train(source, part, regression=True)
    jax.block_until_ready(m_dist.W)
    t_dist = time.perf_counter() - t0
    _emit(
        f"distributed ADMM train {n}x{d}->2x{s} P={P} (world=1)",
        n / t_dist,
        "rows/s",
        t_base / t_dist,
        table,
        contention=None,  # single end-to-end interval per entry point
    )

    # (b) kill right after a committed train chunk, resume, stamp the
    # first post-resume commit (= first completed consensus chunk).
    class _FirstCommit(FaultPlan):
        def __init__(self):
            super().__init__()
            self.t = None

        def after_commit(self, chunk):
            if self.t is None:
                self.t = time.perf_counter()

    with tempfile.TemporaryDirectory() as root:
        ck = dict(checkpoint_dir=root, checkpoint_every=2, prefetch=0)
        try:
            DistributedBlockADMMTrainer(
                "squared", "l2", maps, params, ElasticParams(**ck)
            ).train(
                source, part, regression=True,
                train_fault_plan=FaultPlan(preempt_after_chunk=0),
            )
            raise RuntimeError("train preemption never fired")
        except SimulatedPreemption:
            t_kill = time.perf_counter()
        first = _FirstCommit()
        DistributedBlockADMMTrainer(
            "squared", "l2", maps, params, ElasticParams(resume=True, **ck)
        ).train(source, part, regression=True, train_fault_plan=first)
    _emit(
        "train resume kill-to-first-consensus (world=1)",
        first.t - t_kill,
        "s",
        1.0,
        table,
        contention=None,  # single wall-clock interval, not pooled
    )

    # (c) marginal s/iter of the fused rank step, bf16 vs f32, on the
    # SAME streamed blocks (stream once, factor per dtype; iteration 0
    # absorbs the compile, the rest are steady-state).
    Z_rows, Y_rows, _ = stream_feature_blocks(
        source, maps, part, ElasticParams(prefetch=0), targets=1
    )

    def per_iter(cd):
        prep = prepare_rank_admm(
            "squared", "l2", maps, params, part, 0, Z_rows, Y_rows,
            regression=True, compute_dtype=cd,
        )
        solver = rank_chunked_solver(prep, maps, params)
        st = solver.step_chunk(solver.init_state(), 1)  # compile + warm
        jax.block_until_ready(st["inner"][0])
        k = iters - 1
        t0 = time.perf_counter()
        st = solver.step_chunk(st, k)
        jax.block_until_ready(st["inner"][0])
        return (time.perf_counter() - t0) / k

    t_f32 = per_iter(None)
    t_bf16 = per_iter(jnp.bfloat16)
    _emit(
        f"distributed train step bf16 P={P} 2x{s} feats",
        t_bf16,
        "s/iter",
        t_f32 / t_bf16,
        table,
        contention=None,  # custom timing loop — no burst spread measured
    )


def bench_serve(on_tpu, table):
    """Serving SLO (docs/serving.md): sustained single-row QPS through
    the cross-request coalescing server vs the SAME server pinned serial
    (``max_coalesce=1``), for LS-solve and KRR-predict, with client-side
    p50/p99 submetrics.  The coalescing claim is throughput-shaped — N
    concurrent single-row requests ride ONE fused plan dispatch instead
    of N — so the row to watch is the coalesced/serial QPS ratio
    (``vs_baseline``; the SLO contract targets >= 3x)."""
    import concurrent.futures as cf

    from libskylark_tpu import serve
    from libskylark_tpu.ml.kernels import GaussianKernel
    from libskylark_tpu.ml.model import FeatureMapModel

    m, n = (8192, 64) if on_tpu else (512, 16)
    d, feats = 24, 64
    total = 64 if _SMOKE else 256
    workers = 16
    rng = np.random.default_rng(11)
    A = rng.standard_normal((m, n))
    maps = [GaussianKernel(d, 1.3).create_rft(
        feats, "regular", SketchContext(seed=31)
    )]
    model = FeatureMapModel(
        maps, rng.standard_normal((feats, 4)), scale_maps=True
    )
    rhs = [rng.standard_normal(m) for _ in range(8)]
    xs = [rng.standard_normal(d) for _ in range(8)]

    def drive(make_req, max_coalesce, n_requests=None):
        n = n_requests or total
        params = serve.ServeParams(
            max_coalesce=max_coalesce, max_queue=4 * n,
            warm_start=False, prime=True,
        )
        srv = serve.Server(params, seed=13)
        srv.registry.register_system(
            "sys", A, context=SketchContext(seed=29)
        )
        srv.registry.register_model("mdl", model)
        srv.start()

        def one(i):
            t0 = time.perf_counter()
            r = srv.call(make_req(i))
            dt_ms = (time.perf_counter() - t0) * 1e3
            if not r["ok"]:
                raise RuntimeError(r["error"]["message"])
            return dt_ms

        with cf.ThreadPoolExecutor(max_workers=workers) as pool:
            list(pool.map(one, range(workers)))  # warm every rung first
            t0 = time.perf_counter()
            lat = sorted(pool.map(one, range(n)))
        wall = time.perf_counter() - t0
        srv.stop()
        return (
            n / wall,
            lat[len(lat) // 2],
            lat[min(len(lat) - 1, int(len(lat) * 0.99))],
        )

    cases = [
        ("LS-solve",
         lambda i: serve.make_request("ls_solve", system="sys",
                                      b=rhs[i % len(rhs)])),
        ("KRR-predict",
         lambda i: serve.make_request("predict", model="mdl",
                                      x=xs[i % len(xs)])),
    ]
    for op, mk in cases:
        qps_s, p50_s, p99_s = drive(mk, 1)
        qps_c, p50_c, p99_c = drive(mk, 32)
        _emit(f"serve {op} serial QPS", qps_s, "req/s", 1.0, table,
              contention=None)
        _emit(f"serve {op} coalesced QPS", qps_c, "req/s", qps_c / qps_s,
              table, contention=None)
        _emit(f"serve {op} coalesced p50", p50_c, "ms", p50_s / p50_c,
              table, contention=None)
        _emit(f"serve {op} coalesced p99", p99_c, "ms", p99_s / p99_c,
              table, contention=None)

    # Trace-overhead submetric (docs/observability.md): the SAME
    # coalesced drive, telemetry ON in both modes, tracing isolated by
    # its SKYLARK_TRACE sub-gate — so the ratio charges ONLY what this
    # plane added (mint/span events/flight recorder), not the
    # pre-existing counter+ledger cost.  The SLO contract is
    # vs_baseline >= 0.95 — tracing may cost < 5% QPS — and the
    # minted/finished counts ride the artifact so the traced run proves
    # it actually traced every request (vs_baseline 1.0 there means
    # every minted trace finished into the recorder).
    from libskylark_tpu import telemetry as _tel

    op, mk = cases[0]
    prev = {
        k: os.environ.get(k) for k in ("SKYLARK_TELEMETRY", "SKYLARK_TRACE")
    }
    try:
        # Interleaved A/B, median per mode: one drive is ~100ms of
        # wall, so scheduler jitter would otherwise dwarf the <=5%
        # effect being measured — and sequential best-of-N still
        # confounds the ratio with run-order drift (a box that warms
        # or degrades across the measurement window biases whichever
        # mode ran last).  Alternating modes puts the drift in both.
        os.environ["SKYLARK_TELEMETRY"] = "1"
        qps = {"0": [], "1": []}
        minted = finished = 0
        # 4x-length drives: at ~100ms of wall per drive the OS scheduler
        # is the biggest term in a single sample's variance.
        n_req = (4 * total) if not _SMOKE else total
        for _ in range(3):
            for mode in ("0", "1"):
                os.environ["SKYLARK_TRACE"] = mode
                _tel.reset()
                qps[mode].append(drive(mk, 32, n_requests=n_req)[0])
                if mode == "1":
                    counters = _tel.REGISTRY.snapshot()["counters"]
                    minted += counters.get("trace.minted", 0)
                    finished += counters.get("trace.finished", 0)
        qps_off = sorted(qps["0"])[1]
        qps_on = sorted(qps["1"])[1]
    finally:
        _tel.reset()
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    _emit(f"serve {op} traced QPS", qps_on, "req/s", qps_on / qps_off,
          table, contention=None)
    _emit(f"serve {op} traces minted", minted, "traces",
          (finished / minted) if minted else 0.0, table, contention=None)


def bench_attribution(on_tpu, table):
    """Phase-clock attribution (docs/observability.md, "Latency
    attribution"): the SAME coalesced serve drive, telemetry AND tracing
    held ON in both modes, the per-request phase clock isolated by its
    SKYLARK_PHASES sub-gate — so the ratio charges only what attribution
    added (monotonic stamps, phase histograms) on top of the already-on
    trace plane.  Contract: vs_baseline >= 0.95.  The decomposition row
    then proves the phases mean something: a traced request's recorded
    phases must sum to its own end-to-end latency within 10%
    (``vs_baseline`` there IS the sum/e2e ratio — 1.0 means the phase
    chain tiles the request wall exactly)."""
    import concurrent.futures as cf

    from libskylark_tpu import serve
    from libskylark_tpu import telemetry as _tel

    m, n = (8192, 64) if on_tpu else (512, 16)
    total = 64 if _SMOKE else 256
    workers = 16
    rng = np.random.default_rng(23)
    A = rng.standard_normal((m, n))
    rhs = [rng.standard_normal(m) for _ in range(8)]

    def drive(n_requests):
        params = serve.ServeParams(
            max_coalesce=32, max_queue=4 * n_requests,
            warm_start=False, prime=True,
        )
        srv = serve.Server(params, seed=13)
        srv.registry.register_system(
            "sys", A, context=SketchContext(seed=29)
        )
        srv.start()

        def one(i):
            r = srv.call(serve.make_request(
                "ls_solve", system="sys", b=rhs[i % len(rhs)]
            ))
            if not r["ok"]:
                raise RuntimeError(r["error"]["message"])

        with cf.ThreadPoolExecutor(max_workers=workers) as pool:
            list(pool.map(one, range(workers)))  # warm every rung first
            t0 = time.perf_counter()
            list(pool.map(one, range(n_requests)))
        wall = time.perf_counter() - t0
        srv.stop()
        return n_requests / wall

    prev = {
        k: os.environ.get(k)
        for k in ("SKYLARK_TELEMETRY", "SKYLARK_TRACE", "SKYLARK_PHASES")
    }
    ratio = 0.0
    try:
        # Interleaved A/B, median of 3 per mode, 4x-length drives —
        # the same discipline as the trace-overhead row above:
        # alternating modes puts box-level drift into both samples
        # instead of whichever mode ran last.
        os.environ["SKYLARK_TELEMETRY"] = "1"
        os.environ["SKYLARK_TRACE"] = "1"
        qps = {"0": [], "1": []}
        n_req = (4 * total) if not _SMOKE else total
        for _ in range(3):
            for mode in ("0", "1"):
                os.environ["SKYLARK_PHASES"] = mode
                _tel.reset()
                qps[mode].append(drive(n_req))
        qps_off = sorted(qps["0"])[1]
        qps_on = sorted(qps["1"])[1]

        # Decomposition: one traced request; its phase clock must
        # account for its own end-to-end wall.  Fresh rhs so the
        # front-door cache cannot answer (cache hits carry no phases).
        os.environ["SKYLARK_PHASES"] = "1"
        _tel.reset()
        params = serve.ServeParams(
            max_coalesce=4, warm_start=False, prime=True
        )
        srv = serve.Server(params, seed=13)
        srv.registry.register_system(
            "sys", A, context=SketchContext(seed=29)
        )
        srv.start()
        try:
            srv.call(serve.make_request(
                "ls_solve", system="sys", b=rng.standard_normal(m)
            ))  # warm the rung: the measured request must not compile
            r = srv.call(serve.make_request(
                "ls_solve", system="sys", b=rng.standard_normal(m)
            ))
            envelope = r.get("trace") or {}
            phases = envelope.get("phases") or {}
            e2e = envelope.get("e2e_ms") or 0.0
            if phases and e2e:
                ratio = sum(phases.values()) / e2e
        finally:
            srv.stop()
    finally:
        _tel.reset()
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    _emit("serve phase-clock QPS", qps_on, "req/s", qps_on / qps_off,
          table, contention=None)
    _emit("serve phase sum/e2e", ratio, "ratio", ratio, table,
          contention=None)


def bench_cache(on_tpu, table):
    """Front-door QoS + result cache (docs/serving.md, "QoS + caching").

    Two contracts, two row groups:

    - **Hot-set QPS, cache on vs off**: the same 8-vector hot set driven
      through the same SERIAL server (``max_coalesce=1`` — one dispatch
      per request, so the row isolates the per-dispatch cost the cache
      removes rather than letting coalescing amortise it) twice —
      ``cache=False`` pays a device dispatch per request, ``cache=True``
      re-serves every repeat bitwise from the dict.  ``vs_baseline`` on
      the cache-on row is the speedup; the acceptance floor is 5x on
      CPU.
    - **Adversarial-tenant fairness**: a polite tenant's p99 alone, then
      the SAME polite traffic while a noisy tenant floods the door with
      QoS lanes on (cache off, so the flood is real device work).  The
      deficit-round-robin lanes must keep the polite tenant's p99 within
      2x of its solo p99 (``vs_baseline`` = solo/adversarial >= 0.5) —
      without lanes the polite requests would queue behind the entire
      flood."""
    import concurrent.futures as cf
    import threading

    from libskylark_tpu import serve

    m, n = (8192, 64) if on_tpu else (512, 16)
    total = 64 if _SMOKE else 256
    workers = 16
    rng = np.random.default_rng(17)
    A = rng.standard_normal((m, n))
    hot = [rng.standard_normal(m) for _ in range(8)]

    def req(i, tenant=None):
        r = serve.make_request("ls_solve", system="sys", b=hot[i % len(hot)])
        if tenant is not None:
            r["tenant"] = tenant
        return r

    def make_server(cache_on, max_coalesce=16):
        srv = serve.Server(
            serve.ServeParams(
                max_coalesce=max_coalesce, max_queue=4096, warm_start=False,
                prime=True, cache=cache_on,
                tenant_weights={"polite": 1.0, "noisy": 1.0},
            ),
            seed=13,
        )
        srv.registry.register_system("sys", A, context=SketchContext(seed=29))
        return srv.start()

    def one(srv, i, tenant=None):
        t0 = time.perf_counter()
        r = srv.call(req(i, tenant))
        dt_ms = (time.perf_counter() - t0) * 1e3
        if not r["ok"]:
            raise RuntimeError(r["error"]["message"])
        return dt_ms

    # -- hot-set QPS, cache off vs on ---------------------------------------
    qps = {}
    for cache_on in (False, True):
        srv = make_server(cache_on, max_coalesce=1)
        with cf.ThreadPoolExecutor(max_workers=workers) as pool:
            list(pool.map(lambda i: one(srv, i), range(workers)))  # warm
            t0 = time.perf_counter()
            list(pool.map(lambda i: one(srv, i), range(total)))
            qps[cache_on] = total / (time.perf_counter() - t0)
        hits = srv.cache.stats()["hits"]
        srv.stop()
    _emit("serve cache-off hot-set QPS", qps[False], "req/s", 1.0, table,
          contention=None)
    _emit("serve cache-on hot-set QPS", qps[True], "req/s",
          qps[True] / qps[False], table, contention=None)
    _emit("serve cache hits", hits, "hits",
          hits / (total + workers), table, contention=None)

    # -- adversarial-tenant fairness ----------------------------------------
    def polite_p99(srv):
        with cf.ThreadPoolExecutor(max_workers=4) as pool:
            lat = sorted(pool.map(
                lambda i: one(srv, i, tenant="polite"), range(total // 4)
            ))
        return lat[min(len(lat) - 1, int(len(lat) * 0.99))]

    srv = make_server(False)
    p99_solo = polite_p99(srv)
    stop = threading.Event()

    def flood(j):
        i = 0
        while not stop.is_set():
            one(srv, j * 7919 + i, tenant="noisy")
            i += 1

    flooders = [
        threading.Thread(target=flood, args=(j,), daemon=True)
        for j in range(workers - 4)
    ]
    for t in flooders:
        t.start()
    try:
        p99_mixed = polite_p99(srv)
    finally:
        stop.set()
        for t in flooders:
            t.join(timeout=30)
        srv.stop()
    _emit("serve polite solo p99", p99_solo, "ms", 1.0, table,
          contention=None)
    _emit("serve polite adversarial p99", p99_mixed, "ms",
          p99_solo / p99_mixed, table, contention=None)


def bench_durability(on_tpu, table):
    """Durable serve state (docs/serving.md, "Durable serving"):

    - **Update-op QPS, journal-on vs journal-off**: the same serial
      server driving idempotency-keyed row appends through the wire
      ``update`` op, once process-state only and once with a
      ``state_dir`` — so every mint pays a CRC frame + fsync before it
      publishes.  ``vs_baseline`` on the journal-on row is on/off; the
      acceptance floor is 0.8x (durability may cost at most 20% of
      update throughput at bench scale).
    - **Kill-to-placeable recovery latency**: ``Registry.recover`` wall
      seconds on a state dir holding 1k journaled updates (smoke: 100),
      compaction OFF (pure tail replay) vs compaction ON (snapshot +
      short tail).  ``vs_baseline`` on the compacted row is
      replay/compacted — the snapshot path must not lose to replaying
      every record through the real mutators.
    """
    import shutil
    import tempfile

    from libskylark_tpu import serve
    from libskylark_tpu.serve.journal import Journal
    from libskylark_tpu.serve.registry import Registry

    n_updates = 32 if _SMOKE else 192
    n_recover = 100 if _SMOKE else 1000
    m, n = (2048, 32) if on_tpu else (256, 8)
    rng = np.random.default_rng(17)
    A = rng.standard_normal((m, n))
    rows = [rng.standard_normal((1, n)) for _ in range(8)]

    def drive(state_dir):
        srv = serve.Server(
            serve.ServeParams(warm_start=False, prime=False,
                              state_dir=state_dir),
            seed=13,
        )
        # CWT: the hash-family transform with a columnwise partial
        # rule — FJLT has none and refuses live appends.
        srv.register_system(
            "sys", A, context=SketchContext(seed=29), sketch_type="CWT",
            sketch_size=4 * n, capacity=m + n_updates + 8,
        )
        srv.start()
        # Warm the append path before timing (first call pays traces).
        srv.call(op="update", system="sys", append=rows[0],
                 idem_key="warm")
        t0 = time.perf_counter()
        for i in range(n_updates):
            r = srv.call(op="update", system="sys", append=rows[i % 8],
                         idem_key=f"bench-{i}")
            if not r["ok"]:
                raise RuntimeError(r["error"]["message"])
        wall = time.perf_counter() - t0
        srv.stop()
        return n_updates / wall

    def build_state(directory, compact_every):
        reg = Registry(
            journal=Journal(directory, compact_every=compact_every)
        )
        reg.register_system(
            "sys", A, context=SketchContext(seed=29), sketch_type="CWT",
            sketch_size=4 * n, capacity=m + n_recover + 8,
        )
        for i in range(n_recover):
            reg.append_system_rows("sys", rows[i % 8],
                                   idem=("bench", str(i)))

    with tempfile.TemporaryDirectory() as td:
        qps_off = drive(None)
        qps_on = drive(os.path.join(td, "qps"))
        _emit("serve update QPS journal-off", qps_off, "req/s", 1.0,
              table, contention=None)
        _emit("serve update QPS journal-on", qps_on, "req/s",
              qps_on / qps_off, table, contention=None)
        shutil.rmtree(os.path.join(td, "qps"))

        replay_dir = os.path.join(td, "replay")
        snap_dir = os.path.join(td, "snap")
        build_state(replay_dir, 0)            # journal only: full replay
        build_state(snap_dir, 256)            # snapshot + short tail
        t0 = time.perf_counter()
        reg = Registry.recover(replay_dir)
        t_replay = time.perf_counter() - t0
        assert reg.epoch == n_recover + 1
        t0 = time.perf_counter()
        reg = Registry.recover(snap_dir)
        t_snap = time.perf_counter() - t0
        assert reg.epoch == n_recover + 1
    _emit("serve recovery replay-only", t_replay, "s", 1.0, table,
          contention=None)
    _emit("serve recovery compacted", t_snap, "s", t_replay / t_snap,
          table, contention=None)


def bench_refine(on_tpu, table):
    """Certified mixed-precision refinement vs the exact f64 QR solve
    (docs/performance.md): wall-clock to MATCHED accuracy on the same
    (A, b).  The refine route sketches A once, QR-factors S·A at the
    low working precision, and drives f64 residuals through the
    triangular preconditioner until the guard-certified gate passes;
    the reference is the f64 Householder QR solve of the full system.
    ``vs_baseline`` on the solve row is the speedup (target >= 1.5x on
    CPU); the matched-accuracy row is ``||A x_refine - b|| / ||A
    x_exact - b||`` and must sit at ~1.0 for the speedup to count —
    a fast wrong answer is worth nothing."""
    from jax import enable_x64

    from libskylark_tpu.linalg.least_squares import exact_least_squares
    from libskylark_tpu.solvers.refine import (
        RefineParams,
        refine_least_squares,
    )

    if on_tpu:
        m, n = 32_768, 768
    elif _SMOKE:
        m, n = 2048, 128
    else:
        m, n = 8192, 512
    rounds = 2 if _SMOKE else 5
    rng = np.random.default_rng(23)
    with enable_x64():
        A = jnp.asarray(rng.standard_normal((m, n)))
        b = jnp.asarray(
            A @ rng.standard_normal(n) + 1e-3 * rng.standard_normal(m)
        )

        def run_exact():
            t0 = time.perf_counter()
            X = exact_least_squares(A, b, alg="qr")
            jax.block_until_ready(X)
            return time.perf_counter() - t0, X

        def run_refine():
            t0 = time.perf_counter()
            X, info = refine_least_squares(
                A, b, SketchContext(seed=101), RefineParams()
            )
            jax.block_until_ready(X)
            return time.perf_counter() - t0, X, info

        run_exact(), run_refine()  # compile / plan-cache warmup
        te, Xe = min((run_exact() for _ in range(rounds)),
                     key=lambda r: r[0])
        tr, Xr, info = min((run_refine() for _ in range(rounds)),
                           key=lambda r: r[0])
        r_exact = float(jnp.linalg.norm(A @ Xe - b))
        r_refine = float(jnp.linalg.norm(A @ Xr - b))
    rf = info.get("refine") or {}
    _emit(
        f"refine {m}x{n} mixed-precision solve ({rf.get('rung')}, "
        f"{rf.get('iters')} sweeps)",
        tr * 1e3, "ms", te / tr, table, contention=None,
    )
    _emit(
        "refine matched-accuracy residual",
        r_refine / r_exact if r_exact > 0 else -1.0,
        "ratio", 1.0 if rf.get("converged") else 0.0, table,
        contention=None,
    )


def bench_cond_est(on_tpu, table):
    """Served cond-est QPS (docs/serving.md): the placement-keyed
    cached-probe endpoint under concurrent single-shot load.  The probe
    itself ran once at prime time; every request after it is a dict fan
    through the coalescing batcher, so this row measures the serving
    plane's fixed overhead on its cheapest op."""
    import concurrent.futures as cf

    from libskylark_tpu import serve

    m, n = (8192, 64) if on_tpu else (512, 16)
    total = 64 if _SMOKE else 512
    workers = 16
    rng = np.random.default_rng(7)
    A = rng.standard_normal((m, n))
    params = serve.ServeParams(
        max_coalesce=32, max_queue=4 * total, warm_start=False, prime=True
    )
    srv = serve.Server(params, seed=5)
    srv.registry.register_system("sys", A, context=SketchContext(seed=3))
    srv.start()

    def one(i):
        r = srv.call(serve.make_request("cond_est", system="sys", id=i))
        if not r["ok"]:
            raise RuntimeError(r["error"]["message"])

    with cf.ThreadPoolExecutor(max_workers=workers) as pool:
        list(pool.map(one, range(workers)))  # warm the dispatch path
        t0 = time.perf_counter()
        list(pool.map(one, range(total)))
        wall = time.perf_counter() - t0
    srv.stop()
    _emit(
        "serve cond-est QPS", total / wall, "req/s", 1.0, table,
        contention=None,
    )


def bench_fleet(on_tpu, table):
    """Fleet scaling (docs/serving.md, fleet section): the sustained
    mixed single-row drive (LS-solve + KRR-predict — two placement
    keys, so workers AND replicas both have parallel work) through
    (a) one worker, (b) two device-pinned workers on one admission
    queue, and (c) a 2-replica fleet behind the front-door router.
    ``vs_baseline`` on the (b)/(c) QPS rows is the scaling ratio over
    (a); the acceptance target is >= 1.7x on multi-chip hardware, and
    on a single-device/single-core host the honest ratio is ~1x and
    lands as measured.  The p99 row guards the tail: its ratio is
    p99_1w/p99_2w, so >= 0.67 means the 2-worker tail stayed within
    1.5x of single-worker.  The last row is the device-parallel
    dispatch census: value = sharded programs parity-probed on this
    backend, ratio = fraction that verified bitwise (a tombstoned
    program still serves correct bits through the single-device path,
    so this is hardware truth, not a correctness gate)."""
    import concurrent.futures as cf

    from libskylark_tpu import serve
    from libskylark_tpu import telemetry as _tel
    from libskylark_tpu.ml.kernels import GaussianKernel
    from libskylark_tpu.ml.model import FeatureMapModel
    from libskylark_tpu.serve import dispatch

    m, n = (8192, 64) if on_tpu else (512, 16)
    d, feats = 24, 64
    total = 64 if _SMOKE else 256
    clients = 16
    rng = np.random.default_rng(17)
    A = rng.standard_normal((m, n))
    maps = [GaussianKernel(d, 1.3).create_rft(
        feats, "regular", SketchContext(seed=33)
    )]
    model = FeatureMapModel(
        maps, rng.standard_normal((feats, 4)), scale_maps=True
    )
    rhs = [rng.standard_normal(m) for _ in range(8)]
    xs = [rng.standard_normal(d) for _ in range(8)]

    def make_server(workers):
        srv = serve.Server(
            serve.ServeParams(
                max_coalesce=32, max_queue=8 * total,
                warm_start=False, prime=True, workers=workers,
            ),
            seed=13,
        )
        srv.registry.register_system(
            "sys", A, context=SketchContext(seed=29)
        )
        srv.registry.register_model("mdl", model)
        return srv

    def mk(i):
        if i % 2 == 0:
            return serve.make_request(
                "ls_solve", system="sys", b=rhs[i % len(rhs)]
            )
        return serve.make_request(
            "predict", model="mdl", x=xs[i % len(xs)]
        )

    def drive(front, stoppers):
        def one(i):
            t0 = time.perf_counter()
            r = front.call(mk(i))
            dt_ms = (time.perf_counter() - t0) * 1e3
            if not r["ok"]:
                raise RuntimeError(r["error"]["message"])
            return dt_ms

        try:
            with cf.ThreadPoolExecutor(max_workers=clients) as pool:
                list(pool.map(one, range(clients)))  # warm every rung
                t0 = time.perf_counter()
                lat = sorted(pool.map(one, range(total)))
            wall = time.perf_counter() - t0
        finally:
            for s in stoppers:
                s.stop()
        return (
            total / wall,
            lat[min(len(lat) - 1, int(len(lat) * 0.99))],
        )

    srv1 = make_server(1).start()
    qps1, p99_1 = drive(srv1, [srv1])
    srv2 = make_server(2).start()
    qps2, p99_2 = drive(srv2, [srv2])
    ra, rb = make_server(1).start(), make_server(1).start()
    router = serve.Router()
    router.join("a", server=ra)
    router.join("b", server=rb)
    qps_r, _ = drive(router, [router, ra, rb])

    _emit("serve fleet 1-worker QPS", qps1, "req/s", 1.0, table,
          contention=None)
    _emit("serve fleet 2-worker QPS", qps2, "req/s", qps2 / qps1, table,
          contention=None)
    _emit("serve fleet 2-worker p99", p99_2, "ms", p99_1 / p99_2, table,
          contention=None)
    _emit("serve fleet 2-replica routed QPS", qps_r, "req/s",
          qps_r / qps1, table, contention=None)

    # Device-parallel dispatch census: force the shard gate open, run
    # the same drive once, and count how many sharded programs the
    # one-time parity probe verified bitwise on this backend.
    prev = {
        k: os.environ.get(k)
        for k in ("SKYLARK_SERVE_SHARD", "SKYLARK_TELEMETRY")
    }
    try:
        os.environ["SKYLARK_SERVE_SHARD"] = "1"
        os.environ["SKYLARK_TELEMETRY"] = "1"
        _tel.reset()
        dispatch.clear_cache()
        srv = make_server(1).start()
        drive(srv, [srv])
        counters = _tel.REGISTRY.snapshot()["counters"]
    finally:
        dispatch.clear_cache()
        _tel.reset()
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    verified = counters.get("serve.sharded_verified", 0)
    probed = verified + counters.get("serve.sharded_rejected", 0)
    _emit("serve sharded probes verified", verified, "programs",
          (verified / probed) if probed else 0.0, table, contention=None)


def bench_autoscale(on_tpu, table):
    """Serve through change (docs/serving.md "serve through change" +
    docs/fault_tolerance.md): the round-16 robustness measurements.
    Two registry rows first: wall ms for a live graph edge fold and a
    live LS row append — each publishes a NEW epoch-stamped version
    while in-flight batches keep the old bits (the bitwise contract is
    pinned in tests/test_live_registry.py; this row is what a caller
    pays for it).  Then two fleet rows: scale-up reaction — wall ms
    from a hot p99 signal to the autoscaler's spawned replica joined
    behind the fence (prime-before-placeable, so the number includes
    the full plan-ladder compile); and rolling-drain QPS — the mixed
    drive sustained WHILE the autoscaler drains the fleet 2 -> 1
    mid-traffic.  The ratio on the QPS row is the fraction of calls
    that returned ok; the zero-downtime discipline (drain to zero,
    clean leave, never a 114) makes 1.0 the acceptance target."""
    import concurrent.futures as cf

    from libskylark_tpu import serve
    from libskylark_tpu import telemetry as _tel
    from libskylark_tpu.graph.graph import SimpleGraph
    from libskylark_tpu.serve.registry import Registry

    # -- live-registry epoch bumps (no server: Registry-level timing) --
    nv = 2048 if on_tpu else 256
    ring = [(i, (i + 1) % nv) for i in range(nv)]
    chords = [(i, (i + 7) % nv) for i in range(0, nv, 3)]

    def fold_once():
        reg = Registry()
        reg.register_graph(
            "g", SimpleGraph(ring), k=4, context=SketchContext(seed=5)
        )
        # readback of the refreshed embedding forces the whole delta
        return _timed(lambda: reg.fold_graph_edges("g", chords)[0].X)

    fold_s = min(fold_once() for _ in range(2 if _SMOKE else 3))

    m, n = (8192, 64) if on_tpu else (512, 16)
    blk = 128 if on_tpu else 32
    reps = 2 if _SMOKE else 3
    rng = np.random.default_rng(23)
    A = rng.standard_normal((m, n))
    reg = Registry()
    # SJLT: the only baked-in transform with the columnwise apply_slice
    # a live append needs; capacity reserves sketch-domain rows for it.
    reg.register_system(
        "sys", A, context=SketchContext(seed=3),
        sketch_type="SJLT", capacity=m + (reps + 1) * blk,
    )
    app_s = min(
        _timed(
            lambda: reg.append_system_rows(
                "sys", rng.standard_normal((blk, n))
            )[0].R
        )
        for _ in range(reps)
    )
    _emit(
        f"registry live graph fold {nv}v epoch bump", fold_s * 1e3, "ms",
        1.0, table, contention=None,
    )
    _emit(
        f"registry live row append {blk}x{n} epoch bump", app_s * 1e3,
        "ms", 1.0, table, contention=None,
    )

    # -- autoscaled fleet: scale-up reaction + rolling-drain QPS --
    total = 48 if _SMOKE else 160
    clients = 8
    rhs = [rng.standard_normal(m) for _ in range(8)]

    def make_server():
        srv = serve.Server(
            serve.ServeParams(
                max_coalesce=16, max_queue=8 * total,
                warm_start=False, prime=True, workers=1,
            ),
            seed=13,
        )
        srv.registry.register_system(
            "sys", A, context=SketchContext(seed=29)
        )
        return srv

    prev = os.environ.get("SKYLARK_TELEMETRY")
    stoppers = []
    try:
        # telemetry ON: the p99 the autoscaler steers on only records
        # under the flag, and the shed counter certifies the QPS row.
        os.environ["SKYLARK_TELEMETRY"] = "1"
        _tel.reset()
        core = make_server().start()
        router = serve.Router()
        router.join("core", server=core)
        stoppers = [router, core]
        scaler = serve.Autoscaler(
            router,
            lambda name: make_server(),
            serve.AutoscaleParams(
                min_replicas=1, max_replicas=2,
                queue_high=1e9, queue_low=1e9,
                p99_high_ms=1e-4,  # any recorded latency reads as hot
                cooldown_ticks=0, idle_ticks=10**9,
            ),
        )

        def one(i):
            r = router.call(serve.make_request(
                "ls_solve", system="sys", b=rhs[i % len(rhs)]
            ))
            return bool(r["ok"])

        with cf.ThreadPoolExecutor(max_workers=clients) as pool:
            list(pool.map(one, range(clients)))  # warm + record the p99
            t0 = time.perf_counter()
            for _ in range(64):
                if scaler.step().get("action") == "scale_up":
                    break
            else:
                raise RuntimeError(
                    "autoscaler never scaled up under a hot p99"
                )
            react_ms = (time.perf_counter() - t0) * 1e3
            members = router.fleet_report()["members"]
            if sum(1 for v in members.values() if v.get("placeable")) != 2:
                raise RuntimeError(
                    "scaled-up replica is not placeable behind the fence"
                )

            # flip the loop to idle so it drains back to 1 mid-drive
            scaler.params.p99_high_ms = None
            scaler.params.idle_ticks = 1
            deadline = time.monotonic() + 120.0
            t0 = time.perf_counter()
            futs = [pool.submit(one, i) for i in range(total)]
            while len(router.fleet_report()["members"]) > 1:
                scaler.step()
                if time.monotonic() > deadline:
                    raise RuntimeError("rolling drain did not converge")
                time.sleep(0.002)
            oks = sum(1 for f in futs if f.result())
            wall = time.perf_counter() - t0
        counters = _tel.REGISTRY.snapshot()["counters"]
        shed = counters.get("serve.shed_admission", 0)
        lost = counters.get("router.ejects", 0)
        if shed or lost:
            raise RuntimeError(
                f"rolling drain was not clean (shed={shed}, ejects={lost})"
            )
    finally:
        for s in stoppers:
            s.stop()
        _tel.reset()
        if prev is None:
            os.environ.pop("SKYLARK_TELEMETRY", None)
        else:
            os.environ["SKYLARK_TELEMETRY"] = prev
    _emit(
        "serve autoscale scale-up reaction (prime->placeable)", react_ms,
        "ms", 1.0, table, contention=None,
    )
    _emit(
        "serve autoscale rolling-drain QPS (2->1 mid-traffic)",
        total / wall, "req/s", oks / total, table, contention=None,
    )


def bench_plan_cache(on_tpu, table):
    """Plan-cache cold vs warm: what one compiled sketch-apply plan costs
    to build (trace + compile + first exec) against what the cached
    executable costs per call.  The pair is the observability contract of
    the plan layer: warm ≪ cold is the whole point of caching, and the
    hit/miss counters printed with the rows prove the second call was a
    cache hit, not a silent retrace."""
    from libskylark_tpu import plans
    from libskylark_tpu.sketch.dense import JLT

    if on_tpu:
        m, n, s = 8192, 2048, 512
    else:
        m, n, s = 2048, 256, 64
    # m sits ON the bucket ladder so cold/warm time the same executable
    # shape (no padding asymmetry between the two measurements).
    X = jax.random.normal(jax.random.PRNGKey(7), (m, n), jnp.float32)
    S = JLT(n, s, SketchContext(seed=77))
    S.hoistable_operands(jnp.float32)  # realize operands OUTSIDE the timings

    plans.clear()
    plans.reset_stats()
    cold = _timed(lambda: plans.apply_rowwise_bucketed(S, X))
    st0 = plans.stats()
    warm = min(
        _timed(lambda: plans.apply_rowwise_bucketed(S, X)) for _ in range(10)
    )
    st1 = plans.stats()
    if st0["misses"] < 1 or st1["hits"] < 10:
        raise RuntimeError(
            f"plan cache counters inconsistent (misses={st0['misses']}, "
            f"hits={st1['hits']}); cold/warm split is not trustworthy"
        )
    _emit(
        f"plan-cache cold apply {m}x{n}->{s} (trace+compile+exec)",
        cold * 1e3,
        "ms",
        1.0,
        table,
        contention=None,  # single-shot by construction — cold happens once
    )
    _emit(
        f"plan-cache warm apply {m}x{n}->{s} (cached executable)",
        warm * 1e3,
        "ms",
        cold / warm,  # speedup of the cached path over plan construction
        table,
        contention=None,  # min-of-10 custom loop — no burst spread measured
    )


def bench_guard_overhead(on_tpu, table):
    """What the numerical-health guard costs: guarded vs unguarded
    sketch-and-solve LS on the same problem (docs/numerical_health.md's
    overhead contract).  The guarded run pays one ``certify_sketch``
    (short-budget cond_est on the replicated-small S·A) plus one
    finiteness probe; the emitted value is the guarded/unguarded time
    ratio (1.0 = free).  First capture: vs_baseline fixed at 1.0."""
    from libskylark_tpu.linalg import approximate_least_squares

    if on_tpu:
        m, n = 262_144, 512
    else:
        m, n = 16_384, 128
    A = jax.random.normal(jax.random.PRNGKey(12), (m, n), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(13), (m,), jnp.float32)

    def run():
        return approximate_least_squares(A, b, SketchContext(seed=99))

    prev = os.environ.get("SKYLARK_GUARD")
    try:
        os.environ["SKYLARK_GUARD"] = "0"
        _timed(run)  # compile the sketch+solve programs
        unguarded = min(_timed(run) for _ in range(6))
        os.environ["SKYLARK_GUARD"] = "1"
        _timed(run)  # compile the certification (cond_est) program
        guarded = min(_timed(run) for _ in range(6))
    finally:
        if prev is None:
            os.environ.pop("SKYLARK_GUARD", None)
        else:
            os.environ["SKYLARK_GUARD"] = prev
    _emit(
        f"guard overhead sketch-and-solve LS {m}x{n} (guarded/unguarded)",
        guarded / unguarded,
        "x",
        1.0,
        table,
        contention=None,  # ratio of two min-pooled timings
    )


def bench_telemetry(on_tpu, table):
    """Telemetry-layer submetric: one streamed sketch-and-solve LS pass
    under ``SKYLARK_TELEMETRY=1``, reporting the two derived ratios of
    ``telemetry.snapshot()`` (docs/observability.md): the plan-cache hit
    rate of the pass and the prefetch producer/consumer overlap.  First
    capture: vs_baseline fixed at 1.0 (BASELINE.md records the values)."""
    from libskylark_tpu import plans, telemetry
    from libskylark_tpu.linalg import streaming_least_squares

    if on_tpu:
        n, d, br = 262_144, 512, 32_768
    else:
        n, d, br = 8192, 64, 1024

    def batches(start):
        rng = np.random.default_rng(21)
        for i in range(n // br):
            X = rng.standard_normal((br, d)).astype(np.float32)
            y = rng.standard_normal(br).astype(np.float32)
            if i >= start:
                yield X, y

    prev = os.environ.get("SKYLARK_TELEMETRY")
    os.environ["SKYLARK_TELEMETRY"] = "1"
    telemetry.reset()
    plans.reset()
    try:
        streaming_least_squares(batches, n, d, SketchContext(seed=88))
        snap = telemetry.snapshot()
    finally:
        if prev is None:
            os.environ.pop("SKYLARK_TELEMETRY", None)
        else:
            os.environ["SKYLARK_TELEMETRY"] = prev
    hit = snap["plan_cache_hit_rate"]
    overlap = snap["prefetch_overlap"]
    _emit(
        f"telemetry plan-cache hit rate (streamed LS {n}x{d})",
        hit if hit is not None else -1,
        "ratio",
        1.0,
        table,
        contention=None,  # counter ratio, not a timing
    )
    _emit(
        f"telemetry prefetch overlap (streamed LS {n}x{d})",
        overlap if overlap is not None else -1,
        "ratio",
        1.0,
        table,
        contention=None,  # counter ratio, not a timing
    )


def bench_policy(on_tpu, table):
    """Adaptive-policy submetric (docs/autotuning.md): the same guarded
    sketch-and-solve LS pass run cold (empty profile store + empty plan
    cache) and warm (after ``policy.warm_start`` replays the persisted
    hot plans), reporting the plan-compile seconds each pass pays, plus
    the profile-learned sketch-dimension ratio once the store matures.
    Warm < cold is the warm-start contract of ISSUE 9; the dim ratio
    shows the autotuner actually shrinking toward the smallest
    certified-OK size.  First capture: vs_baseline fixed at 1.0."""
    import shutil
    import tempfile

    from libskylark_tpu import plans, policy
    from libskylark_tpu.linalg import approximate_least_squares

    if on_tpu:
        m, n = 65_536, 256
    else:
        m, n = 4096, 64
    A = jax.random.normal(jax.random.PRNGKey(31), (m, n), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(32), (m,), jnp.float32)

    env_keys = ("SKYLARK_POLICY", "SKYLARK_POLICY_DIR",
                "SKYLARK_POLICY_MIN_SAMPLES", "SKYLARK_GUARD")
    saved = {k: os.environ.get(k) for k in env_keys}
    tmp = tempfile.mkdtemp(prefix="skylark-bench-policy-")
    os.environ["SKYLARK_POLICY"] = "1"
    os.environ["SKYLARK_GUARD"] = "1"
    os.environ.pop("SKYLARK_POLICY_DIR", None)
    os.environ["SKYLARK_POLICY_MIN_SAMPLES"] = "3"
    try:
        prev_xla_cache = jax.config.jax_compilation_cache_dir
    except Exception:  # noqa: BLE001 — knob absent on old jax
        prev_xla_cache = False  # sentinel: don't restore
    try:
        policy.configure(tmp)
        policy.reset()
        policy.invalidate_cache()

        # -- cold: empty store, empty plan cache; the pass pays every
        # plan trace+compile itself.  A fresh same-seed context per call
        # keeps the sketch (and so the plan keys) bitwise identical
        # between the cold and warm passes.
        plans.clear()
        plans.reset_stats()
        approximate_least_squares(A, b, SketchContext(seed=41))
        cold = plans.stats()["compile_seconds"]
        policy.flush()  # persist the profile + hot-plan records

        # -- warm: new "process" (cleared plan cache + merged-view
        # reload), replay the recorded plans, then run the same pass.
        # Its compile seconds are what warm start did NOT save.
        plans.clear()
        policy.invalidate_cache()
        ws = policy.warm_start(tmp)
        plans.reset_stats()
        approximate_least_squares(A, b, SketchContext(seed=41))
        st = plans.stats()
        warm = st["compile_seconds"]
        if ws["plans_replayed"] < 1 or st["hits"] < 1:
            raise RuntimeError(
                f"warm start replayed {ws['plans_replayed']} plans, "
                f"{st['hits']} hits; cold/warm split is not trustworthy"
            )
        _emit(
            f"policy cold LS pass {m}x{n} plan-compile",
            cold * 1e3, "ms", 1.0, table,
            contention=None,  # single-shot by construction
        )
        _emit(
            f"policy warm LS pass {m}x{n} plan-compile (after replay)",
            warm * 1e3, "ms",
            # compile seconds warm start removed; a perfect replay pays
            # 0.0 warm, so the speedup is floored at the 1ms resolution
            # the compile timer can meaningfully distinguish.
            cold / max(warm, 1e-3),
            table,
            contention=None,
        )

        # -- autotuned sketch dimension: mature the profile past
        # min_samples and read the decided/default ratio of the next
        # pass (shrink-toward-smallest-certified-OK, decide.py).
        for k in range(3):
            approximate_least_squares(A, b, SketchContext(seed=41))
        policy.flush()
        policy.invalidate_cache()
        _, info = approximate_least_squares(
            A, b, SketchContext(seed=41), return_info=True
        )
        dec = info["policy"]
        _emit(
            f"policy sketch-dim ratio LS {m}x{n} (decided/default, "
            f"source={dec['source']})",
            dec["sketch_size"] / min(4 * n, m), "ratio", 1.0, table,
            contention=None,  # a decision, not a timing
        )
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        policy.configure(None)
        policy.reset()
        policy.invalidate_cache()
        if prev_xla_cache is not False:
            # warm_start fills the XLA cache knob when unset; put back
            # whatever the process had (tmp is about to be deleted).
            try:
                jax.config.update("jax_compilation_cache_dir", prev_xla_cache)
            except Exception:  # noqa: BLE001
                pass
        shutil.rmtree(tmp, ignore_errors=True)


def bench_elastic_resume(on_tpu, table):
    """Elastic-resume submetric (docs/fault_tolerance.md): a world=1
    partitioned streaming fold is preempted mid-pass right after a chunk
    commit, then resumed from the per-host checkpoints; the emitted value
    is wall-seconds from the kill to the FIRST post-resume fold landing —
    the restore + ledger-replay latency a real preempted host pays before
    it makes forward progress again.  Dry-run scale on purpose: the cost
    is dominated by checkpoint restore and plan/ledger I/O, not FLOPs.
    First capture: vs_baseline fixed at 1.0."""
    import tempfile

    from libskylark_tpu.plans import accumulate_slice
    from libskylark_tpu.resilient import FaultPlan, SimulatedPreemption
    from libskylark_tpu.sketch.hash import CWT
    from libskylark_tpu.streaming import ElasticParams, RowPartition
    from libskylark_tpu.streaming.elastic import elastic_run_stream

    n, d, br = 8192, 64, 512  # 16 batches, preempt after chunk 7
    rng = np.random.default_rng(77)
    A = rng.standard_normal((n, d))
    blocks = [jnp.asarray(A[lo : lo + br]) for lo in range(0, n, br)]
    S = CWT(n, 256, SketchContext(seed=77))
    part = RowPartition(nrows=n, batch_rows=br, world_size=1)
    init = {
        "sa": jnp.zeros((S.s, d), jnp.float32),
        "row": np.asarray(0, np.int64),
    }
    first_fold: list[float] = []

    def step(acc, block, index):
        row = int(acc["row"])
        out = {
            "sa": accumulate_slice(S, acc["sa"], block, row),
            "row": np.asarray(row + block.shape[0], np.int64),
        }
        if not first_fold:
            jax.block_until_ready(out["sa"])
            first_fold.append(time.perf_counter())
        return out

    def factory(start):
        return iter(blocks[start:])

    with tempfile.TemporaryDirectory() as root:
        params = ElasticParams(
            checkpoint_dir=root, checkpoint_every=1, prefetch=0
        )
        try:
            elastic_run_stream(
                factory, step, init, part, params,
                fault_plan=FaultPlan(preempt_after_chunk=7),
            )
            raise RuntimeError("preemption never fired")
        except SimulatedPreemption:
            t_kill = time.perf_counter()
        first_fold.clear()
        elastic_run_stream(
            factory, step, init, part,
            ElasticParams(
                checkpoint_dir=root, checkpoint_every=1, prefetch=0,
                resume=True,
            ),
        )
    _emit(
        f"elastic resume kill-to-first-fold (world=1, {n}x{d})",
        first_fold[0] - t_kill,
        "s",
        1.0,
        table,
        contention=None,  # single wall-clock interval, not pooled
    )


def bench_graph(on_tpu, table):
    """Graph-analytics rows (docs/graph.md): (a) streamed edge-fold
    sketch throughput (edges/s) vs the dense route on the SAME graph —
    the dense baseline materializes the (n, n) adjacency and applies
    the sketch to it, which is the pre-streaming in-core path; the
    streamed fold touches O(edges) and must win by >= 1.3x even on CPU
    (``vs_baseline`` is the speedup).  (b) Elastic ASE kill-resume:
    wall-seconds from a mid-pass preemption to the FIRST post-resume
    edge fold landing (same shape as the elastic-resume row, over the
    graph fold).  (c) Served PPR QPS, coalesced vs serial — same-seed
    riders share one memoized diffusion, so the coalesced server
    answers N concurrent requests with ~1 solve."""
    import concurrent.futures as cf
    import tempfile

    from libskylark_tpu import serve
    from libskylark_tpu.graph import SimpleGraph
    from libskylark_tpu.graph.stream import (
        adjacency_sketch_fold,
        graph_block_source,
        streamed_adjacency_sketch,
    )
    from libskylark_tpu.resilient import FaultPlan, SimulatedPreemption
    from libskylark_tpu.sketch.hash import SJLT
    from libskylark_tpu.streaming import ElasticParams, RowPartition
    from libskylark_tpu.streaming.elastic import elastic_run_stream

    n, m = (16384, 400_000) if on_tpu else (2048, 30_000)
    if _SMOKE:
        n, m = 256, 2_000
    s = 128
    rng = np.random.default_rng(23)
    G = SimpleGraph(map(tuple, rng.integers(0, n, (m, 2)).tolist()))
    E = G.volume // 2
    S = SJLT(G.n, s, SketchContext(seed=23))
    src = graph_block_source(G, batch_edges=max(E, 1))

    def streamed():
        return streamed_adjacency_sketch(src, S, ncols=G.n)

    def dense():
        return S.apply(jnp.asarray(G.adjacency()), "columnwise")

    _timed(streamed), _timed(dense)  # compile both routes
    reps = 1 if _SMOKE else 3
    t_st = min(_timed(streamed) for _ in range(reps))
    t_dn = min(_timed(dense) for _ in range(reps))
    _emit(
        f"graph streamed sketch ({E} edges, n={G.n}, s={s})",
        E / t_st, "edges/s", t_dn / t_st, table, contention=None,
    )

    # (b) kill -> first post-resume fold, world=1 edge partition.
    br = max(E // 16, 1)
    init_at, step = adjacency_sketch_fold(S, G.n)
    part = RowPartition(nrows=E, batch_rows=br, world_size=1)
    first_fold: list[float] = []

    def timed_step(acc, block, index):
        out = step(acc, block, index)
        if not first_fold:
            jax.block_until_ready(out["sa"])
            first_fold.append(time.perf_counter())
        return out

    fold_src = graph_block_source(G, batch_edges=br)
    with tempfile.TemporaryDirectory() as root:
        try:
            elastic_run_stream(
                fold_src, timed_step, init_at(0), part,
                ElasticParams(
                    checkpoint_dir=root, checkpoint_every=1, prefetch=0
                ),
                fault_plan=FaultPlan(preempt_after_chunk=3),
            )
            raise RuntimeError("preemption never fired")
        except SimulatedPreemption:
            t_kill = time.perf_counter()
        first_fold.clear()
        elastic_run_stream(
            fold_src, timed_step, init_at(0), part,
            ElasticParams(
                checkpoint_dir=root, checkpoint_every=1, prefetch=0,
                resume=True,
            ),
        )
    _emit(
        f"graph ASE resume kill-to-first-fold ({E} edges)",
        first_fold[0] - t_kill, "s", 1.0, table, contention=None,
    )

    # (c) served PPR QPS: coalesced vs serial, fresh same-seed servers.
    total = 16 if _SMOKE else 96
    workers = 16
    Gq = SimpleGraph(
        map(tuple, rng.integers(0, 256, (2_000, 2)).tolist())
    )

    def drive(max_coalesce):
        srv = serve.Server(
            serve.ServeParams(
                max_coalesce=max_coalesce, max_queue=4 * total,
                warm_start=False,
            ),
            seed=23,
        )
        srv.register_graph("g", Gq, k=8)
        srv.start()

        def one(i):
            r = srv.call(op="ppr", graph="g", seeds=[i % 8])
            if not r["ok"]:
                raise RuntimeError(r["error"]["message"])

        with cf.ThreadPoolExecutor(max_workers=workers) as pool:
            list(pool.map(one, range(8)))  # warm the memo per seed
            t0 = time.perf_counter()
            list(pool.map(one, range(total)))
            wall = time.perf_counter() - t0
        srv.stop()
        return total / wall

    qps_s = drive(1)
    qps_c = drive(32)
    _emit("serve graph PPR serial QPS", qps_s, "req/s", 1.0, table,
          contention=None)
    _emit("serve graph PPR coalesced QPS", qps_c, "req/s", qps_c / qps_s,
          table, contention=None)


_FINAL: dict | None = None
_FINAL_PRINTED = False


def _print_final() -> None:
    """Print the LAST line (headline + full submetrics table) exactly once.

    Also wired to SIGTERM: if an outer ``timeout`` fires anyway, the
    driver still records a complete final line with everything measured
    so far (the round-3 rc=124 artifact lost the headline entirely)."""
    global _FINAL_PRINTED
    if _FINAL is None or _FINAL_PRINTED:
        return
    _FINAL_PRINTED = True
    print(json.dumps(_FINAL), flush=True)


def main() -> None:
    global _FINAL
    failed_rows = 0  # any failed row makes the exit code non-zero

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not _SMOKE:
        print(
            f"bench.py measures a TPU; the first device is "
            f"{dev.platform!r}.  (SKYLARK_BENCH_SMOKE=1 runs the artifact "
            "path at toy sizes on any backend.)",
            file=sys.stderr,
        )
        sys.exit(2)
    peak = _peak_tflops(dev) if on_tpu else None
    table: list[dict] = []

    def _flush_on_term(signum, frame):
        _print_final()
        sys.exit(1)  # cut short: not a whole run

    # A driver timeout still flushes a parseable final line.
    provisional = {
        "metric": "JLT dense sketch-apply throughput "
        "(FAILED: killed-before-headline)",
        "value": -1,
        "unit": "error",
        "vs_baseline": 0,
    }
    _FINAL = dict(provisional, submetrics=[dict(provisional)])
    signal.signal(signal.SIGTERM, _flush_on_term)

    def _failed(name: str, e: BaseException) -> None:
        nonlocal failed_rows
        failed_rows += 1
        _emit(
            f"{name} (FAILED: {type(e).__name__})", -1, "error", 0, table,
            contention=None,
        )

    # -- flagships FIRST: a budget/timeout can no longer eat the rows the
    # driver exists to record.  A failing config degrades to a FAILED
    # row so the rest of the list still runs — and the exit code says so.
    try:
        tflops, _ = bench_jlt(on_tpu, table)
        headline_row = {
            "metric": "JLT dense sketch-apply throughput",
            "value": round(float(tflops), 3),
            "unit": "TFLOP/s/chip",
            "vs_baseline": round(float(tflops) / peak, 4) if peak else 0.0,
        }
        if _LAST_CONTENTION is not None:
            headline_row["contention"] = _LAST_CONTENTION
    except Exception as e:  # noqa: BLE001 — recorded, and exit code != 0
        failed_rows += 1
        headline_row = {
            "metric": (
                f"JLT dense sketch-apply throughput (FAILED: {type(e).__name__})"
            ),
            "value": -1,
            "unit": "error",
            "vs_baseline": 0,
        }
    headline_row["backend"] = str(jax.default_backend())
    table.append(dict(headline_row))
    print(json.dumps(headline_row), flush=True)
    # submetrics aliases the LIVE table: rows appended below are included
    # when the final line prints (or the SIGTERM flush fires).
    _FINAL = dict(headline_row, submetrics=table)

    if not _selected("streaming KRR"):
        _emit(
            "streaming KRR (skipped: filter)", -1, "skipped", 0, table,
            contention=None,
        )
    else:
        try:
            bench_streaming_krr(on_tpu, table)
        except Exception as e:  # noqa: BLE001 — recorded, exit code != 0
            _failed("streaming KRR", e)

    # -- secondaries, descending importance.  Each carries a rough cost
    # estimate (compile + pooled measurement, seconds); when the
    # remaining budget cannot plausibly fit a config it emits an
    # explicit skip row instead of dying mid-list.
    # Never-captured rows ride near the front (VERDICT r4 item 3: QRFT /
    # RLT sat at positions 13-14 for three rounds and never landed; the
    # FJLT f32 row also moves up — it is the round-5 fused-kernel
    # measurement).  Rows with round-2/3 captures queue behind them.
    secondaries = [
        # Round-18 rows lead (never captured): the front-door result
        # cache + multi-tenant QoS lanes (docs/serving.md, "QoS +
        # caching") — hot-set QPS cache-on vs off, and the
        # adversarial-tenant fairness p99 pair.
        # Round-20 rows lead (never captured): latency attribution
        # (docs/observability.md, "Latency attribution") — phase-clock
        # on/off QPS (floor 0.95x) and the phase-decomposition
        # sum/e2e ratio.
        ("serve attribution", 60,
         lambda: bench_attribution(on_tpu, table)),
        # Round-19 rows lead (never captured): durable serve state
        # (docs/serving.md, "Durable serving") — update-op QPS with the
        # write-ahead journal on vs off (floor 0.8x) and
        # kill-to-placeable recovery latency, compacted vs replay-only.
        ("serve durability", 60, lambda: bench_durability(on_tpu, table)),
        ("serve cache", 60, lambda: bench_cache(on_tpu, table)),
        # Round-17 rows next (never captured): elastic multi-host
        # BlockADMM training (docs/distributed_training.md) — world=1
        # rows/s vs the in-process solver, kill-to-first-consensus
        # resume latency, and the bf16 train-step submetric.
        ("distributed train", 120, lambda: bench_train(on_tpu, table)),
        # Round-16 rows next (never captured): chaos-driven autoscaler +
        # epoch-versioned live registries (docs/serving.md, "serve
        # through change") — live fold/append epoch-bump latency,
        # scale-up reaction, and rolling-drain QPS.
        ("serve autoscale", 60, lambda: bench_autoscale(on_tpu, table)),
        # Round-15 row next (never captured): streamed graph sketching
        # + elastic ASE resume + served PPR QPS (docs/graph.md).
        ("graph analytics", 60, lambda: bench_graph(on_tpu, table)),
        # Round-14 rows next (never captured): the certified
        # mixed-precision refine solve (docs/performance.md) and the
        # served cond-est endpoint (docs/serving.md).
        ("refine solve", 60, lambda: bench_refine(on_tpu, table)),
        ("serve cond-est", 40, lambda: bench_cond_est(on_tpu, table)),
        # Plan-cache cold/warm first among the never-captured rows: it is
        # the round-6 perf-layer measurement and costs almost nothing.
        ("plan cache", 40, lambda: bench_plan_cache(on_tpu, table)),
        # Guard overhead next among never-captured rows: the round-6
        # robustness-layer measurement (docs/numerical_health.md).
        ("guard overhead", 60, lambda: bench_guard_overhead(on_tpu, table)),
        # Telemetry ratios ride with the never-captured rows: cheap, and
        # they certify the observability layer on real hardware.
        ("telemetry", 60, lambda: bench_telemetry(on_tpu, table)),
        # Adaptive-policy cold/warm rides with the never-captured rows:
        # the round-9 warm-start contract (docs/autotuning.md) — plan
        # compile seconds with and without the profile-store replay.
        ("policy", 60, lambda: bench_policy(on_tpu, table)),
        # Serving SLO rides with the never-captured rows: the round-10
        # throughput contract (docs/serving.md) — coalesced vs serial
        # QPS with p50/p99 for single-row LS-solve and KRR-predict.
        ("serve SLO", 90, lambda: bench_serve(on_tpu, table)),
        # Fleet scaling rides behind it: the round-13 measurement
        # (docs/serving.md fleet section) — 2 pinned workers and a
        # 2-replica routed fleet vs one worker, plus the sharded-
        # dispatch parity-probe census.
        ("serve fleet", 90, lambda: bench_fleet(on_tpu, table)),
        # Elastic resume latency rides with them: the round-7
        # fault-tolerance measurement (docs/fault_tolerance.md), world=1
        # dry-run scale so it costs seconds, not minutes.
        ("elastic resume", 30, lambda: bench_elastic_resume(on_tpu, table)),
        # Fused stream-chunk rides with the never-captured rows: the
        # round-8 kernel-layer measurement (fused single-launch chunks
        # vs the two-step composite on identical data).
        ("fused stream-chunk", 90, lambda: bench_stream_chunk(on_tpu, table)),
        # Overlapped streaming rides with it: the round-11 measurement
        # (async-dispatch overlap vs per-step sync on identical data,
        # plus the hidden-transfer-fraction submetric).
        ("stream overlap", 90, lambda: bench_overlap(on_tpu, table)),
        ("streaming SVD", 150, lambda: bench_streaming_svd(on_tpu, table)),
        ("sparse CWT", 150, lambda: bench_sparse_cwt(on_tpu, table)),
        ("QRFT", 90, lambda: bench_qrft(on_tpu, table)),
        ("RLT", 80, lambda: bench_rlt(on_tpu, table)),
        ("FJLT f32", 90, lambda: bench_fjlt(on_tpu, jnp.float32, 44.8, table)),
        ("FJLT bf16", 80, lambda: bench_fjlt(on_tpu, jnp.bfloat16, 5.9, table)),
        ("CWT", 80, lambda: bench_cwt(on_tpu, table)),
        ("MMT", 80, lambda: bench_mmt(on_tpu, table)),
        ("FastRFT bf16", 100, lambda: bench_frft(on_tpu, jnp.bfloat16, 16.1, table)),
        ("PPT bf16", 120, lambda: bench_ppt(on_tpu, jnp.bfloat16, 70.7, table)),
        ("FastRFT f32", 120, lambda: bench_frft(on_tpu, jnp.float32, 51.2, table)),
        ("PPT f32", 150, lambda: bench_ppt(on_tpu, jnp.float32, 149.4, table)),
        ("ridge", 80, lambda: bench_ridge(on_tpu, table)),
        ("ADMM", 160, lambda: bench_admm(on_tpu, table)),
    ]
    for name, est_s, fn in secondaries:
        if not _selected(name):
            _emit(
                f"{name} (skipped: filter)", -1, "skipped", 0, table,
                contention=None,
            )
            continue
        if on_tpu and _remaining() < 0.6 * est_s:
            _emit(
                f"{name} (skipped: budget)", -1, "skipped", 0, table,
                contention=None,
            )
            continue
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — recorded, exit code != 0
            _failed(name, e)

    _print_final()
    if failed_rows:
        sys.exit(1)


if __name__ == "__main__":
    main()
