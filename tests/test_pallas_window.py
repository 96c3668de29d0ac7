"""Pallas window-kernel + fused stream-chunk tests, interpret mode.

Tier-1 on CPU CI (ISSUE 8): ``interpret=True`` executes the kernel body
as traced jax ops, so the grid/BlockSpec plumbing, the scalar-loop
accumulate, the padding seams, and the fused-emit bitwise contract are
all exercised on every PR — not only under SKYLARK_RUN_PERF=1 on TPU.
The compiled-lowering half of the battery lives in
``tests/_hw_guards.py`` and ``tests/test_tpu_compile.py``.

x64 is on (conftest), so every array here is built f32 explicitly — the
window kernel's default dtype gate routes f64 to XLA on purpose.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from libskylark_tpu import plans, streaming
from libskylark_tpu.core.context import SketchContext
from libskylark_tpu.core.precision import f32_accumulable
from libskylark_tpu.resilient import FaultPlan
from libskylark_tpu.sketch import pallas_window
from libskylark_tpu.sketch.hash import (
    CWT,
    MMT,
    SJLT,
    WZT,
    _segment_sum_rows,
    _window_mode,
)
from libskylark_tpu.streaming import StreamParams

pytestmark = pytest.mark.kernels


@pytest.fixture
def window_interpret():
    """Force the window kernel in interpret mode for the duration of a
    test; the plan key carries the env token, but clear the cache anyway
    so cross-test state can't mask a routing bug."""
    old = os.environ.get("SKYLARK_PALLAS_WINDOW")
    os.environ["SKYLARK_PALLAS_WINDOW"] = "interpret"
    plans.clear()
    try:
        yield
    finally:
        if old is None:
            del os.environ["SKYLARK_PALLAS_WINDOW"]
        else:
            os.environ["SKYLARK_PALLAS_WINDOW"] = old
        plans.clear()


def _rand(rng, shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dtype)


# ---------------------------------------------------------------------------
# kernel vs XLA reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "k,s,m",
    [
        (7, 12, 5),      # tiny ragged streaming chunk
        (130, 10, 1),    # single-column (the LS driver's sb vector)
        (1000, 96, 200), # off-tile m
        (257, 8, 384),   # multi-lane-tile, S below one sublane tile
        (2048, 1000, 130),  # S off the 8-sublane grid, k over one chunk
    ],
)
def test_scatter_rows_matches_segment_sum(rng, k, s, m):
    A = _rand(rng, (k, m))
    b = jnp.asarray(rng.integers(0, s, k), jnp.int32)
    v = _rand(rng, k)
    out = pallas_window.scatter_rows(A, b, v, s, interpret=True)
    ref = jax.ops.segment_sum(v[:, None] * A, b, num_segments=s)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


def test_scatter_rows_hot_bucket(rng):
    """Every entry lands in one bucket — the scalar loop's worst-case
    RMW chain must still sum exactly in entry order."""
    k, s, m = 300, 16, 24
    A = _rand(rng, (k, m))
    v = _rand(rng, k)
    b = jnp.full((k,), 11, jnp.int32)
    out = pallas_window.scatter_rows(A, b, v, s, interpret=True)
    ref = jax.ops.segment_sum(v[:, None] * A, b, num_segments=s)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )
    assert np.all(np.asarray(out[:11]) == 0) and np.all(
        np.asarray(out[12:]) == 0
    )


def test_scatter_rows_acc_fold_bitwise(rng):
    """The fused emit (acc + scratch inside the kernel) must be BITWISE
    the unfused composite — this is the whole basis of the fused
    stream-chunk path's planned≡eager contract."""
    k, s, m = 500, 40, 36
    A = _rand(rng, (k, m))
    b = jnp.asarray(rng.integers(0, s, k), jnp.int32)
    v = _rand(rng, k)
    acc = _rand(rng, (s, m))
    part = pallas_window.scatter_rows(A, b, v, s, interpret=True)
    fused = pallas_window.scatter_rows(A, b, v, s, acc=acc, interpret=True)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(acc + part))


def test_scatter_rows_bf16_input(rng):
    """bf16 operand, f32 accumulate: the cast in is exact, so the result
    matches the f32 reference of the upcast operand."""
    k, s, m = 320, 17, 40
    A = _rand(rng, (k, m), jnp.bfloat16)
    b = jnp.asarray(rng.integers(0, s, k), jnp.int32)
    v = _rand(rng, k)
    out = pallas_window.scatter_rows(A, b, v, s, interpret=True)
    ref = jax.ops.segment_sum(
        v[:, None] * A.astype(jnp.float32), b, num_segments=s
    )
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


def test_scatter_rows_rejects_non_f32_acc(rng):
    A = _rand(rng, (8, 4))
    b = jnp.zeros((8,), jnp.int32)
    v = _rand(rng, 8)
    acc = jnp.zeros((4, 4), jnp.float64)
    with pytest.raises(TypeError, match="float32"):
        pallas_window.scatter_rows(A, b, v, 4, acc=acc, interpret=True)


def test_window_self_check_interpret():
    assert pallas_window.self_check(2048, 257, 96, interpret=True) < 1e-5


# ---------------------------------------------------------------------------
# stacked multi-hash scatter (SJLT, nnz > 1) — ISSUE 11
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("nnz", [2, 3, 4])
def test_scatter_rows_stacked_matches_segment_sum(rng, nnz):
    k, s, m = 500, 40, 36
    A = _rand(rng, (k, m))
    b = jnp.asarray(rng.integers(0, s, (nnz, k)), jnp.int32)
    v = _rand(rng, (nnz, k))
    out = pallas_window.scatter_rows(A, b, v, s, interpret=True)
    ref = jax.ops.segment_sum(
        (v[:, :, None] * A[None, :, :]).reshape(-1, m),
        b.reshape(-1),
        num_segments=s,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


def test_scatter_rows_stacked_nnz1_degenerates(rng):
    """A (1, k) stacked call is the SAME layout as the 1-D call — bitwise,
    not just numerically (the nnz=1 fast path must not fork)."""
    k, s, m = 257, 24, 17
    A = _rand(rng, (k, m))
    b = jnp.asarray(rng.integers(0, s, k), jnp.int32)
    v = _rand(rng, k)
    flat = pallas_window.scatter_rows(A, b, v, s, interpret=True)
    stacked = pallas_window.scatter_rows(A, b[None, :], v[None, :], s,
                                         interpret=True)
    np.testing.assert_array_equal(np.asarray(flat), np.asarray(stacked))


def test_scatter_rows_stacked_acc_fold_bitwise(rng):
    """The fused emit holds for nnz > 1 too: acc + part in one launch is
    bitwise acc + part in two."""
    k, s, m, nnz = 300, 16, 24, 3
    A = _rand(rng, (k, m))
    b = jnp.asarray(rng.integers(0, s, (nnz, k)), jnp.int32)
    v = _rand(rng, (nnz, k))
    acc = _rand(rng, (s, m))
    part = pallas_window.scatter_rows(A, b, v, s, interpret=True)
    fused = pallas_window.scatter_rows(A, b, v, s, acc=acc, interpret=True)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(acc + part))


def test_scatter_rows_stacked_shape_mismatch_rejected(rng):
    A = _rand(rng, (8, 4))
    b = jnp.zeros((2, 8), jnp.int32)
    v = _rand(rng, (3, 8))
    with pytest.raises(ValueError, match="shape"):
        pallas_window.scatter_rows(A, b, v, 4, interpret=True)


def test_stacked_self_check_interpret():
    assert pallas_window.self_check(1000, 96, 40, nnz=3, interpret=True) < 1e-5


def test_sjlt_kernel_path_matches_xla_path(rng, window_interpret):
    """SJLT (nnz=4) through the stacked single-launch kernel agrees with
    the XLA per-hash fold (different kernels — tolerance, not bits)."""
    S = SJLT(N, S_OUT, SketchContext(seed=5))
    A = _rand(rng, (N, M))
    kern = S.apply_slice(A[:7], 0)
    os.environ["SKYLARK_PALLAS_WINDOW"] = "0"
    xla = S.apply_slice(A[:7], 0)
    scale = float(jnp.max(jnp.abs(xla))) or 1.0
    np.testing.assert_allclose(
        np.asarray(kern), np.asarray(xla), rtol=1e-5, atol=1e-5 * scale
    )


def test_rowwise_kernel_path_matches_xla_path(rng, window_interpret):
    """ROWWISE dense apply normalizes to the sublane scatter by one
    transpose; kernel vs XLA on the same sketch, tolerance not bits."""
    S = _hash(CWT)
    A = _rand(rng, (9, N))
    kern = S.apply(A, "rowwise")
    os.environ["SKYLARK_PALLAS_WINDOW"] = "0"
    xla = S.apply(A, "rowwise")
    scale = float(jnp.max(jnp.abs(xla))) or 1.0
    np.testing.assert_allclose(
        np.asarray(kern), np.asarray(xla), rtol=1e-5, atol=1e-5 * scale
    )


# ---------------------------------------------------------------------------
# FJLT sampled-transform gather epilogue — ISSUE 11
# ---------------------------------------------------------------------------


def test_gather_scaled_rows_bitwise_xla(rng):
    """The gather kernel is pure row selection + one elementwise multiply
    in the same dtype — bitwise EQUAL to the XLA take, by contract."""
    nrows, s, m = 600, 48, 36
    T = _rand(rng, (nrows, m))
    idx = jnp.asarray(rng.integers(0, nrows, s), jnp.int32)
    scale = jnp.float32(0.3125)
    out = pallas_window.gather_scaled_rows(T, idx, scale, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(scale * T[idx, :])
    )


def test_gather_self_check_interpret():
    assert pallas_window.self_check_gather(interpret=True) == 0.0


def test_gather_gates():
    # (R_pad * TM) must fit the VMEM budget: 2000*384 does, a
    # million-row source does not
    assert pallas_window.supported_gather(2000, 512, 320)
    assert not pallas_window.supported_gather(1_000_000, 512, 320)
    assert pallas_window.worthwhile_gather(2000, 4096, 320)
    assert not pallas_window.worthwhile_gather(2000, 8, 320)


def test_fjlt_gather_epilogue_bitwise_xla(rng, monkeypatch):
    """FJLT's sampled-transform epilogue through the gather kernel must
    be bitwise the XLA sampling of the same transform output."""
    from libskylark_tpu.sketch import fjlt as fjlt_mod

    n, s, m = 64, 24, 7
    A = _rand(rng, (n, m))
    monkeypatch.setenv("SKYLARK_NO_SRHT_GEMM", "1")
    monkeypatch.setenv("SKYLARK_PALLAS_GATHER", "0")
    S = fjlt_mod.FJLT(n, s, SketchContext(seed=9))
    xla = S.apply(A, "columnwise")
    monkeypatch.setenv("SKYLARK_PALLAS_GATHER", "interpret")
    S2 = fjlt_mod.FJLT(n, s, SketchContext(seed=9))
    kern = S2.apply(A, "columnwise")
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(xla))


# ---------------------------------------------------------------------------
# dispatcher routing (static decisions only)
# ---------------------------------------------------------------------------


def test_window_mode_defaults_to_xla_off_tpu():
    if jax.default_backend() == "tpu":
        pytest.skip("TPU default routing is probed on hardware")
    assert _window_mode(1000, 64, 128, jnp.float32) == "xla"


def test_window_mode_forced_and_disabled(window_interpret):
    assert _window_mode(1000, 64, 128, jnp.float32) == "interpret"
    assert _window_mode(1000, 64, 128, jnp.bfloat16) == "interpret"
    # f64 demotes ONLY under a forced mode
    assert _window_mode(1000, 64, 128, jnp.float64) == "interpret"
    os.environ["SKYLARK_PALLAS_WINDOW"] = "0"
    assert _window_mode(1000, 64, 128, jnp.float32) == "xla"
    os.environ["SKYLARK_PALLAS_WINDOW"] = ""
    assert _window_mode(1000, 64, 128, jnp.float64) == "xla"
    os.environ["SKYLARK_NO_PALLAS"] = "1"
    try:
        os.environ["SKYLARK_PALLAS_WINDOW"] = "interpret"
        assert _window_mode(1000, 64, 128, jnp.float32) == "xla"
    finally:
        del os.environ["SKYLARK_NO_PALLAS"]


def test_f32_accumulable_gate():
    assert f32_accumulable(jnp.float32)
    assert f32_accumulable(jnp.bfloat16)
    assert f32_accumulable(jnp.float16)
    assert not f32_accumulable(jnp.float64)
    assert f32_accumulable(jnp.float64, demote_f64=True)
    assert not f32_accumulable(jnp.int32)


def test_segment_sum_rows_oversized_falls_back(window_interpret):
    """A sketch dimension past the VMEM gate must route to XLA even
    under a forced mode — forced honors `supported`, not `worthwhile`."""
    big_s = 5_000_000
    assert not pallas_window.supported(100, big_s, 128)
    assert _window_mode(100, 128, big_s, jnp.float32) == "xla"


# ---------------------------------------------------------------------------
# hash dispatcher: eager / kernel-path / planned-fused parity
# ---------------------------------------------------------------------------

N, S_OUT, M = 40, 12, 5
RAGGED = (7, 7, 7, 7, 7, 5)  # covers N with a ragged tail


def _hash(cls, seed=5):
    return cls(N, S_OUT, SketchContext(seed=seed))


@pytest.mark.parametrize("cls", [CWT, MMT, WZT])
def test_slice_kernel_matches_eager_dispatch(rng, cls, window_interpret):
    """apply_slice (eager, concrete start) and apply_slice_kernel
    (traced-start form) route through the same dispatcher mode, so on
    in-domain windows they are bitwise identical."""
    S = _hash(cls)
    A = _rand(rng, (N, M))
    start = 7
    blk = A[start : start + 7]
    eager = S.apply_slice(blk, start)
    kern = S.apply_slice_kernel(blk, jnp.asarray(start, jnp.int32))
    np.testing.assert_array_equal(np.asarray(eager), np.asarray(kern))


@pytest.mark.parametrize("cls", [CWT, MMT, WZT])
def test_kernel_path_matches_xla_path(rng, cls, window_interpret):
    """The interpret-kernel slice must agree numerically with the XLA
    slice of the same window (different kernels — tolerance, not bits)."""
    S = _hash(cls)
    A = _rand(rng, (N, M))
    kern = S.apply_slice(A[:7], 0)
    os.environ["SKYLARK_PALLAS_WINDOW"] = "0"
    xla = S.apply_slice(A[:7], 0)
    scale = float(jnp.max(jnp.abs(xla))) or 1.0
    np.testing.assert_allclose(
        np.asarray(kern), np.asarray(xla), rtol=1e-5, atol=1e-5 * scale
    )


@pytest.mark.parametrize("cls", [CWT, MMT, WZT, SJLT])
def test_planned_fused_bitwise_eager_ragged(rng, cls, window_interpret):
    """THE fused-chunk contract: planned-fused accumulation over ragged
    batches is bitwise the eager composite fold (CWT/MMT/WZT take the
    single-launch fused kernel; SJLT nnz=4 rides the SAME launch with
    its hashes stacked on the sublane grid — ISSUE 11)."""
    S = _hash(cls)
    A = _rand(rng, (N, M))
    acc_e = jnp.zeros((S_OUT, M), jnp.float32)
    acc_p = jnp.zeros((S_OUT, M), jnp.float32)
    start = 0
    for k in RAGGED:
        blk = A[start : start + k]
        acc_e = acc_e + S.apply_slice(blk, start).astype(jnp.float32)
        acc_p = plans.accumulate_slice(S, acc_p, blk, start)
        start += k
    np.testing.assert_array_equal(np.asarray(acc_e), np.asarray(acc_p))
    # and the fold still matches the one-shot apply numerically
    np.testing.assert_allclose(
        np.asarray(acc_p), np.asarray(S.apply(A)), rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize("cls", [CWT, WZT])
def test_fused_vs_unfused_plans_bitwise(rng, cls, window_interpret):
    """SKYLARK_NO_FUSED_CHUNKS / fused=False is a pure kill switch: the
    two-step composite plan produces the same bits as the fused plan."""
    S = _hash(cls)
    A = _rand(rng, (N, M))
    accs = {True: jnp.zeros((S_OUT, M), jnp.float32),
            False: jnp.zeros((S_OUT, M), jnp.float32)}
    start = 0
    for k in RAGGED:
        blk = A[start : start + k]
        for fused in (True, False):
            accs[fused] = plans.accumulate_slice(
                S, accs[fused], blk, start, fused=fused
            )
        start += k
    np.testing.assert_array_equal(
        np.asarray(accs[True]), np.asarray(accs[False])
    )


def test_default_path_unchanged_without_env(rng):
    """With no forcing env, CPU routing stays XLA end to end — the
    planned≡eager contract of the pre-kernel code must be untouched."""
    assert _window_mode(7, M, S_OUT, jnp.float64) == "xla"
    S = _hash(CWT)
    A = jnp.asarray(rng.standard_normal((N, M)))  # f64 under x64
    acc_e = jnp.zeros((S_OUT, M), A.dtype)
    acc_p = jnp.zeros((S_OUT, M), A.dtype)
    start = 0
    for k in RAGGED:
        blk = A[start : start + k]
        acc_e = acc_e + S.apply_slice(blk, start)
        acc_p = plans.accumulate_slice(S, acc_p, blk, start)
        start += k
    np.testing.assert_array_equal(np.asarray(acc_e), np.asarray(acc_p))


# ---------------------------------------------------------------------------
# fused chunks through the streaming drivers + guard replay
# ---------------------------------------------------------------------------


def _ls_stream_factory(A, b, nbatches):
    rows = A.shape[0] // nbatches

    def factory(start):
        return iter(
            [
                (
                    jnp.asarray(A[i * rows : (i + 1) * rows], jnp.float32),
                    jnp.asarray(b[i * rows : (i + 1) * rows], jnp.float32),
                )
                for i in range(start, nbatches)
            ]
        )

    return factory


@pytest.mark.guard
def test_guard_replay_through_fused_kernel_bit_identical(
    rng, window_interpret
):
    """Sentinel replay of a poisoned batch through the FUSED stream-
    chunk kernel stays bit-identical to the clean pass (satellite 4):
    CWT + f32 accumulators, so the single-launch fused path serves both
    the original fold and the guard's replay."""
    m, n, nb = 240, 6, 8
    A = rng.normal(size=(m, n))
    b = A @ rng.normal(size=n) + 1e-3 * rng.normal(size=m)
    factory = _ls_stream_factory(A, b, nb)

    def run(fault_plan=None):
        S = CWT(m, 4 * n, SketchContext(seed=3))
        return streaming.sketch_least_squares(
            factory, S, ncols=n, dtype=jnp.float32, fault_plan=fault_plan
        )

    x0, info0 = run()
    assert info0["recovery"]["recovered"] is False
    x1, info1 = run(FaultPlan(nan_at=3))
    rec = info1["recovery"]
    assert rec["recovered"] is True
    assert any(a["action"] == "replay" for a in rec["attempts"])
    np.testing.assert_array_equal(np.asarray(x0), np.asarray(x1))


@pytest.mark.streaming
def test_stream_params_fused_kill_switch(rng, window_interpret):
    """StreamParams(fused_chunks=False) threads through the drivers and
    produces the same bits as the fused default."""
    from libskylark_tpu import streaming

    S = _hash(CWT)
    A = rng.standard_normal((N, M)).astype(np.float32)

    def run(fused):
        blocks = [
            jnp.asarray(A[lo : lo + 7]) for lo in range(0, N, 7)
        ]
        return streaming.sketch(
            lambda start: iter(blocks[start:]), S, ncols=M,
            dtype=jnp.float32,
            params=StreamParams(fused_chunks=fused),
        )

    np.testing.assert_array_equal(
        np.asarray(run(True)), np.asarray(run(False))
    )
