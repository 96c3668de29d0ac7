"""Plan-layer tests: the bitwise contract, the retrace guard, and the
cache's observability counters.

The plan layer's hard promise (``docs/performance.md``) is that compiling
an apply changes WHEN the math runs, never WHAT it computes: planned
results are bit-for-bit the eager results, and a streaming pass traces
once per bucket shape, not once per batch.  Everything here runs on the
CPU test mesh and is tier-1 except the ``perf``-marked wall-clock check
(machine-sensitive; opt in with ``SKYLARK_RUN_PERF=1``).
"""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from libskylark_tpu import plans
from libskylark_tpu.core.context import SketchContext
from libskylark_tpu.sketch import CWT, FJLT, JLT, MMT, GaussianRFT


def _mk(cls, n, s, seed=11, **kw):
    return cls(n, s, SketchContext(seed=seed), **kw)


def _fjlt_streamed(n, s):
    """An FJLT past its SRHT-GEMM gate: diagonal, factored WHT, row sample
    and rescale — the route Blendenpik's sketch takes at the benchmark's
    shape (small shapes take the GEMM route and would pin something else)."""
    S = _mk(FJLT, n, s)
    assert not S._gemm_wins(jnp.float32) and not S._gemm_wins(jnp.float64)
    return S


SMALL = (96, 48, 37, jnp.float64)  # n, s, m, dtype
# One linear dense, two hash-based, one feature map and one fast transform:
# together they cover the matmul, segment-sum, pointwise-epilogue and
# WHT-and-gather plan bodies.
TRANSFORMS = [
    pytest.param(lambda n, s: _mk(JLT, n, s), SMALL, id="JLT"),
    pytest.param(lambda n, s: _mk(CWT, n, s), SMALL, id="CWT"),
    pytest.param(lambda n, s: _mk(MMT, n, s), SMALL, id="MMT"),
    pytest.param(
        lambda n, s: _mk(GaussianRFT, n, s, sigma=1.3), SMALL,
        id="GaussianRFT",
    ),
    pytest.param(_fjlt_streamed, (4096, 2048, 24, jnp.float32), id="FJLT-wht-f32"),
    pytest.param(_fjlt_streamed, (4096, 2048, 24, jnp.float64), id="FJLT-wht-f64"),
]


class TestBitwiseParity:
    """planned == eager, to the bit, both dims (the hard contract)."""

    @pytest.mark.parametrize("dim", ["columnwise", "rowwise"])
    @pytest.mark.parametrize("make,sizes", TRANSFORMS)
    def test_planned_equals_eager(self, make, sizes, dim, rng):
        n, s, m, dtype = sizes
        S = make(n, s)
        shape = (n, m) if dim == "columnwise" else (m, n)
        A = jnp.asarray(rng.standard_normal(shape), dtype)
        eager = np.asarray(S.apply(A, dim))
        planned = np.asarray(plans.apply(S, A, dim))
        np.testing.assert_array_equal(planned, eager)
        # The cached second call runs the same executable: same bits.
        np.testing.assert_array_equal(
            np.asarray(plans.apply(S, A, dim)), eager
        )

    @pytest.mark.parametrize("k", [5, 20, 33, 48])
    def test_rowwise_bucketed_bitwise(self, k, rng):
        # Real rows of a bucket-padded batch are bitwise the eager ragged
        # apply: row-independent applies + exact-zero padding.
        n, s = 24, 32
        S = _mk(JLT, n, s, seed=3)
        X = jnp.asarray(rng.standard_normal((k, n)))
        eager = np.asarray(S.apply(X, "rowwise"))
        Z = np.asarray(plans.apply_rowwise_bucketed(S, X))
        assert Z.shape == eager.shape
        np.testing.assert_array_equal(Z, eager)

    def test_pad_out_zeroes_dead_rows(self, rng):
        S = _mk(GaussianRFT, 16, 24, seed=7, sigma=0.9)
        X = jnp.asarray(rng.standard_normal((13, 16)))
        Zp, k = plans.apply_rowwise_bucketed(S, X, pad_out=True)
        assert k == 13
        assert Zp.shape[0] == plans.bucket_rows(13)
        np.testing.assert_array_equal(np.asarray(Zp[13:]), 0.0)
        np.testing.assert_array_equal(
            np.asarray(Zp[:13]), np.asarray(S.apply(X, "rowwise"))
        )


class TestRetraceGuard:
    """Ragged streaming batches compile once per BUCKET, never per batch."""

    # 8 ragged batch sizes covering 4 ladder buckets (12, 24, 32, 48).
    SIZES = [23, 17, 40, 9, 31, 25, 30, 25]

    def test_one_trace_per_bucket(self, rng):
        n, m, s = sum(self.SIZES), 12, 32
        S = _mk(CWT, n, s, seed=13)
        A = rng.standard_normal((n, m))
        buckets = {plans.bucket_rows(k) for k in self.SIZES}
        assert len(self.SIZES) >= 8 > len(buckets)

        plans.clear()  # count traces of a fresh cache from zero

        def one_pass():
            acc = jnp.zeros((s, m))
            row = 0
            for k in self.SIZES:
                acc = plans.accumulate_slice(
                    S, acc, jnp.asarray(A[row : row + k]), row
                )
                row += k
            return acc

        acc = one_pass()
        st1 = plans.stats()
        assert st1["bypasses"] == 0, "slice path unexpectedly fell back"
        assert st1["traces"] <= len(buckets)
        assert st1["misses"] == len(buckets)

        # Second pass: every plan is a cache hit, zero new traces.
        acc2 = one_pass()
        st2 = plans.stats()
        assert st2["traces"] == st1["traces"]
        assert st2["misses"] == st1["misses"]
        assert st2["hits"] >= st1["hits"] + len(self.SIZES)

        # Same executables, same accumulation order: identical bits; and
        # the streamed sum matches the in-core apply to fp round-off.
        np.testing.assert_array_equal(np.asarray(acc), np.asarray(acc2))
        full = np.asarray(S.apply(jnp.asarray(A), "columnwise"))
        np.testing.assert_allclose(np.asarray(acc), full, atol=1e-10)

    def test_rowwise_one_trace_per_bucket(self, rng):
        n, s = 20, 16
        S = _mk(JLT, n, s, seed=17)
        plans.clear()
        buckets = {plans.bucket_rows(k) for k in self.SIZES}
        for k in self.SIZES:
            plans.apply_rowwise_bucketed(
                S, jnp.asarray(rng.standard_normal((k, n)))
            )
        st = plans.stats()
        assert st["traces"] <= len(buckets)
        assert st["misses"] == len(buckets)


class TestCacheObservability:
    """stats() counters: monotone, bypass-aware, LRU-bounded."""

    def test_counters_monotone_and_env_bypass(self, rng, monkeypatch):
        monkeypatch.delenv("SKYLARK_NO_PLANS", raising=False)
        S = _mk(JLT, 32, 16, seed=9)
        A = jnp.asarray(rng.standard_normal((32, 7)))
        st0 = plans.stats()
        plans.apply(S, A, "columnwise")
        plans.apply(S, A, "columnwise")
        st1 = plans.stats()
        for key in (
            "hits", "misses", "evictions", "traces", "compiles",
            "compile_seconds", "bypasses",
        ):
            assert st1[key] >= st0[key], key
        assert st1["hits"] + st1["misses"] >= st0["hits"] + st0["misses"] + 2

        # SKYLARK_NO_PLANS=1 turns the layer into a counted pass-through.
        monkeypatch.setenv("SKYLARK_NO_PLANS", "1")
        assert not plans.enabled()
        st2 = plans.stats()
        out = plans.apply(S, A, "columnwise")
        st3 = plans.stats()
        assert st3["bypasses"] == st2["bypasses"] + 1
        assert st3["hits"] == st2["hits"]
        assert st3["misses"] == st2["misses"]
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(S.apply(A, "columnwise"))
        )
        monkeypatch.delenv("SKYLARK_NO_PLANS")
        assert plans.enabled()

    def test_lru_eviction(self, rng):
        S = _mk(JLT, 16, 8, seed=21)
        old_max = plans.stats()["max_size"]
        plans.clear()
        try:
            plans.set_cache_size(2)
            for m in (3, 4, 5, 6):  # 4 distinct shape keys, bound 2
                plans.apply(
                    S, jnp.asarray(rng.standard_normal((16, m))), "columnwise"
                )
            st = plans.stats()
            assert st["size"] <= 2
            assert st["evictions"] >= 2
        finally:
            plans.set_cache_size(old_max)

    def test_hoistable_operands_memoized(self):
        S = _mk(JLT, 32, 16, seed=5)
        a = S.hoistable_operands(jnp.dtype("float64"))
        b = S.hoistable_operands(jnp.dtype("float64"))
        assert a is b  # one realization per (sketch, dtype) per process
        c = S.hoistable_operands(jnp.dtype("float32"))
        assert c is not a
        assert c.dtype == jnp.float32


class TestBucketing:
    def test_ladder_is_geometric_and_monotone(self):
        lad = plans.bucket_ladder(4096)
        assert lad[0] == 8
        assert all(a < b for a, b in zip(lad, lad[1:]))
        # padding overhead is bounded: consecutive rungs within 1.5x
        assert all(b <= a * 1.5 + 1e-9 for a, b in zip(lad, lad[1:]))

    def test_bucket_rows_respects_gates(self):
        # padding must never cross an algorithm gate: 15 stays 15 with a
        # gate at 16 (padding to 16 would flip the one-hot/scatter choice)
        assert plans.bucket_rows(15, (16,)) == 15
        assert plans.bucket_rows(17, (16,)) >= 17
        assert plans.bucket_rows(12) == 12  # on the ladder already


@pytest.mark.perf
class TestPerfTimings:
    """Wall-clock assertions — machine-sensitive, SKYLARK_RUN_PERF=1 only."""

    def test_warm_apply_beats_cold(self, rng):
        S = _mk(JLT, 256, 64, seed=33)
        X = jnp.asarray(rng.standard_normal((512, 256)))
        plans.clear()
        t0 = time.perf_counter()
        np.asarray(plans.apply_rowwise_bucketed(S, X))
        cold = time.perf_counter() - t0
        warm = min(
            (lambda t: (np.asarray(plans.apply_rowwise_bucketed(S, X)),
                        time.perf_counter() - t)[1])(time.perf_counter())
            for _ in range(5)
        )
        assert warm < cold, (warm, cold)
