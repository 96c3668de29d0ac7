"""Streaming engine tests: partial-sketch equivalence, drivers, prefetch
pipeline, and kill-and-resume.

The lock is the counter contract's streaming analogue: a sketch applied
block-by-block through ``apply_slice`` + merge must equal the whole-matrix
apply (exactly for ROWWISE concat, to summation-order rounding for
COLUMNWISE sums), and a pass killed mid-stream and resumed from its
checkpoint must be BIT-FOR-BIT the uninterrupted pass (same fold order).
All on small synthetic data — tier-1.
"""

import time

import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import sparse as jsparse

from libskylark_tpu import sketch as sk
from libskylark_tpu import streaming
from libskylark_tpu.core import SketchContext
from libskylark_tpu.streaming import Prefetcher, StreamParams, skip_batches

pytestmark = pytest.mark.streaming

N, M, S_OUT = 40, 5, 12
BATCH = 7  # deliberately does not divide N (last block is ragged)


def blocks_of(*arrays, batch=BATCH):
    n = arrays[0].shape[0]
    out = []
    for lo in range(0, n, batch):
        sl = tuple(a[lo : lo + batch] for a in arrays)
        out.append(sl[0] if len(arrays) == 1 else sl)
    return out


def make_transform(kind, n, s, ctx):
    if kind == "GaussianRFT":
        return sk.GaussianRFT(n, s, ctx, sigma=1.3)
    return sk.create_sketch(kind, n, s, context=ctx)


# ---------------------------------------------------------------------------
# partial-sketch protocol
# ---------------------------------------------------------------------------


class TestPartialSketchEquivalence:
    KINDS = ["JLT", "CT", "CWT", "MMT", "WZT", "GaussianRFT"]

    @pytest.mark.parametrize("kind", KINDS)
    def test_columnwise_stream_matches_whole(self, kind, rng):
        ctx = SketchContext(seed=5)
        S = make_transform(kind, N, S_OUT, ctx)
        A = jnp.asarray(rng.standard_normal((N, M)))
        want = np.asarray(S.apply(A, "columnwise"))
        got = streaming.sketch(blocks_of(A), S, "columnwise", ncols=M)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-12, atol=1e-12)

    @pytest.mark.parametrize("kind", KINDS)
    def test_rowwise_stream_matches_whole(self, kind, rng):
        ctx = SketchContext(seed=6)
        S = make_transform(kind, N, S_OUT, ctx)
        A = jnp.asarray(rng.standard_normal((17, N)))  # rows carry full N
        want = np.asarray(S.apply(A, "rowwise"))
        got = streaming.sketch(blocks_of(A, batch=5), S, "rowwise")
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-12, atol=1e-12)

    def test_columnwise_sparse_blocks(self, rng):
        ctx = SketchContext(seed=7)
        S = make_transform("CWT", N, S_OUT, ctx)
        A = rng.standard_normal((N, M))
        A[rng.random((N, M)) < 0.6] = 0.0
        want = np.asarray(S.apply(jnp.asarray(A), "columnwise"))
        sparse_blocks = [
            jsparse.BCOO.fromdense(jnp.asarray(b)) for b in blocks_of(A)
        ]
        got = streaming.sketch(sparse_blocks, S, "columnwise", ncols=M)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-12, atol=1e-12)

    def test_apply_slice_bounds_checked(self, rng):
        S = make_transform("JLT", N, S_OUT, SketchContext(seed=8))
        block = jnp.asarray(rng.standard_normal((BATCH, M)))
        with pytest.raises(ValueError, match="outside the sketch domain"):
            S.apply_slice(block, N - 2, "columnwise")
        with pytest.raises(ValueError, match="outside the sketch domain"):
            S.apply_slice(block, -1, "columnwise")

    def test_unsupported_transform_says_so(self, rng):
        from libskylark_tpu.utils.exceptions import UnsupportedError

        S = sk.create_sketch("FJLT", 64, 16, context=SketchContext(seed=9))
        with pytest.raises(UnsupportedError, match="partial-sketch"):
            S.apply_slice(jnp.zeros((8, 3)), 0, "columnwise")

    def test_row_count_mismatch_rejected(self, rng):
        S = make_transform("JLT", N, S_OUT, SketchContext(seed=10))
        A = jnp.asarray(rng.standard_normal((N - BATCH, M)))  # short stream
        with pytest.raises(ValueError, match="sketch domain"):
            streaming.sketch(blocks_of(A), S, "columnwise", ncols=M)


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------


class TestStreamingDrivers:
    def test_least_squares_matches_direct_sketch_solve(self, rng):
        from libskylark_tpu.linalg import streaming_least_squares
        from libskylark_tpu.linalg.least_squares import (
            LeastSquaresParams,
            exact_least_squares,
        )

        n, d = 60, 4
        A = rng.standard_normal((n, d))
        b = A @ rng.standard_normal(d) + 0.01 * rng.standard_normal(n)
        params = LeastSquaresParams(sketch_type="JLT", sketch_size=16)
        x, info = streaming_least_squares(
            blocks_of(jnp.asarray(A), jnp.asarray(b)), n, d,
            SketchContext(seed=11), params,
        )
        assert info["rows"] == n and info["batches"] == -(-n // BATCH)
        # fresh context, same seed: contexts are stateful counter
        # reservers, so the reference sketch must not share one
        S = sk.create_sketch("JLT", n, 16, context=SketchContext(seed=11))
        want = exact_least_squares(
            S.apply(jnp.asarray(A), "columnwise"),
            S.apply(jnp.asarray(b)[:, None], "columnwise"),
        )[:, 0]
        np.testing.assert_allclose(np.asarray(x), np.asarray(want), rtol=1e-10)

    def test_kernel_ridge_matches_incore(self, rng):
        from libskylark_tpu.ml import kernel_by_name
        from libskylark_tpu.ml.krr import (
            approximate_kernel_ridge,
            streaming_approximate_kernel_ridge,
        )

        n, d, s = 50, 3, 32
        X = rng.standard_normal((n, d))
        y = rng.standard_normal(n)
        kernel = kernel_by_name("gaussian", d, sigma=1.0)
        model_in = approximate_kernel_ridge(
            kernel, jnp.asarray(X), jnp.asarray(y), 0.1, s,
            SketchContext(seed=12),
        )
        model_st = streaming_approximate_kernel_ridge(
            kernel, blocks_of(jnp.asarray(X), jnp.asarray(y)), 0.1, s,
            SketchContext(seed=12),
        )
        assert model_st.info["rows"] == n
        np.testing.assert_allclose(
            np.asarray(model_st.predict(jnp.asarray(X))),
            np.asarray(model_in.predict(jnp.asarray(X))),
            rtol=1e-8, atol=1e-10,
        )

    def test_empty_stream_raises(self):
        S = make_transform("JLT", N, S_OUT, SketchContext(seed=13))
        with pytest.raises(ValueError, match="empty stream"):
            streaming.sketch([], S, "rowwise")

    def test_rowwise_checkpoint_rejected(self, tmp_path):
        S = make_transform("JLT", N, S_OUT, SketchContext(seed=14))
        with pytest.raises(ValueError, match="rowwise"):
            streaming.sketch(
                [], S, "rowwise",
                params=StreamParams(checkpoint_dir=str(tmp_path)),
            )

    def test_one_shot_iterable_cannot_reopen(self):
        factory = streaming.as_block_factory(iter([1, 2, 3]))
        assert list(factory(0)) == [1, 2, 3]
        with pytest.raises(ValueError, match="one-shot"):
            factory(0)
        factory2 = streaming.as_block_factory([1, 2])
        with pytest.raises(ValueError, match="one-shot"):
            factory2(1)  # starting past 0 needs a real factory


# ---------------------------------------------------------------------------
# kill-and-resume (riding the resilient runtime)
# ---------------------------------------------------------------------------


@pytest.mark.faults
class TestKillAndResume:
    def _factory(self, A):
        def factory(start):
            return skip_batches(iter(blocks_of(A)), start) if start \
                else iter(blocks_of(A))

        return factory

    @pytest.mark.parametrize("kind", ["JLT", "CWT", "GaussianRFT"])
    def test_resumed_pass_is_bitwise_identical(self, kind, tmp_path, rng):
        from libskylark_tpu.resilient import FaultPlan, SimulatedPreemption

        ctx = SketchContext(seed=15)
        S = make_transform(kind, N, S_OUT, ctx)
        A = jnp.asarray(rng.standard_normal((N, M)))
        want = np.asarray(
            streaming.sketch(self._factory(A), S, "columnwise", ncols=M)
        )

        ck = str(tmp_path / f"ck_{kind}")
        params = StreamParams(checkpoint_dir=ck, checkpoint_every=2)
        with pytest.raises(SimulatedPreemption):
            streaming.sketch(
                self._factory(A), S, "columnwise", ncols=M, params=params,
                fault_plan=FaultPlan(preempt_after_chunk=1),
            )
        got = streaming.sketch(
            self._factory(A), S, "columnwise", ncols=M,
            params=StreamParams(
                checkpoint_dir=ck, checkpoint_every=2, resume=True
            ),
        )
        np.testing.assert_array_equal(np.asarray(got), want)

    def test_least_squares_resume(self, tmp_path, rng):
        from libskylark_tpu.linalg import streaming_least_squares
        from libskylark_tpu.linalg.least_squares import LeastSquaresParams
        from libskylark_tpu.resilient import FaultPlan, SimulatedPreemption

        n, d = 60, 4
        A = jnp.asarray(rng.standard_normal((n, d)))
        b = jnp.asarray(rng.standard_normal(n))
        lsp = LeastSquaresParams(sketch_type="CWT", sketch_size=16)
        # fresh context per call: contexts are stateful counter
        # reservers, and each call creates its own sketch
        ctx = lambda: SketchContext(seed=16)  # noqa: E731

        def factory(start):
            it = iter(blocks_of(A, b))
            return skip_batches(it, start) if start else it

        want, _ = streaming_least_squares(factory, n, d, ctx(), lsp)
        ck = str(tmp_path / "ck")
        with pytest.raises(SimulatedPreemption):
            streaming_least_squares(
                factory, n, d, ctx(), lsp,
                stream_params=StreamParams(
                    checkpoint_dir=ck, checkpoint_every=2,
                ),
                fault_plan=FaultPlan(preempt_after_chunk=1),
            )
        got, info = streaming_least_squares(
            factory, n, d, ctx(), lsp,
            stream_params=StreamParams(
                checkpoint_dir=ck, checkpoint_every=2, resume=True
            ),
        )
        assert info["rows"] == n
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# prefetch pipeline
# ---------------------------------------------------------------------------


class TestPrefetcher:
    def test_order_and_exhaustion(self):
        items = list(range(25))
        with Prefetcher(iter(items), depth=3, placer=None) as pf:
            assert list(pf) == items
        assert pf.stats.consumed == len(items)
        assert pf.stats.produced == len(items)
        assert pf.stats.hits + pf.stats.waits >= len(items)

    def test_producer_exception_propagates(self):
        def source():
            yield 1
            raise RuntimeError("disk on fire")

        pf = Prefetcher(source(), depth=2, placer=None)
        assert next(pf) == 1
        with pytest.raises(RuntimeError, match="disk on fire"):
            for _ in pf:
                pass
        pf.close()

    def test_backpressure_bounds_readahead(self):
        pulled = []

        def source():
            for i in range(100):
                pulled.append(i)
                yield i

        depth = 2
        pf = Prefetcher(source(), depth=depth, placer=None)
        assert next(pf) == 0
        time.sleep(0.3)  # let the producer run as far as it can
        # ≤ depth staged + 1 in the producer's hand + the 1 consumed
        assert len(pulled) <= depth + 2
        pf.close()
        assert len(pulled) < 100  # close() released the thread early

    def test_placer_applied(self):
        pf = Prefetcher(iter([1, 2]), depth=1, placer=lambda x: x * 10)
        assert list(pf) == [10, 20]

    def test_overlap_smoke(self):
        """The overlap proof: with IO time ≈ compute time, the pipelined
        wall clock must beat the serial sum, and at least one batch must
        already be staged when asked for (stats.hits)."""
        nbatch, io_s, compute_s = 8, 0.03, 0.03

        def source():
            for i in range(nbatch):
                time.sleep(io_s)  # simulated parse + transfer
                yield i

        # The serial sum is MEASURED, the same sleeps one after another on
        # the same machine at the same moment: on a loaded host a 30 ms
        # sleep takes what it takes (one tier-1 run read 0.85 s against
        # the nominal 0.48 s), in both loops alike.
        t0 = time.perf_counter()
        for _ in source():
            time.sleep(compute_s)
        serial = max(time.perf_counter() - t0, nbatch * (io_s + compute_s))

        t0 = time.perf_counter()
        pf = Prefetcher(source(), depth=2, placer=None)
        for _ in pf:
            time.sleep(compute_s)  # simulated device compute
        wall = time.perf_counter() - t0
        assert wall < 0.9 * serial, (
            f"no overlap: wall {wall:.3f}s vs serial {serial:.3f}s "
            f"(stats: {pf.stats})"
        )
        assert pf.stats.hits >= 1, f"never found a staged batch: {pf.stats}"


class TestStreamParams:
    def test_prefetch_knobs_ride_resilient_params(self, tmp_path):
        p = StreamParams(
            prefetch=4, checkpoint_dir=str(tmp_path), checkpoint_every=3
        )
        assert p.prefetch == 4
        assert p.checkpoint_dir == str(tmp_path)
        assert p.checkpoint_every == 3

    def test_stream_with_prefetch_disabled(self, rng):
        S = make_transform("JLT", N, S_OUT, SketchContext(seed=17))
        A = jnp.asarray(rng.standard_normal((N, M)))
        want = np.asarray(S.apply(A, "columnwise"))
        got = streaming.sketch(
            blocks_of(A), S, "columnwise", ncols=M,
            params=StreamParams(prefetch=0),
        )
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-12)
