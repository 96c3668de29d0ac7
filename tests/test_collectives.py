"""shard_map sketch schedules + panel-blocked dense apply + linear CLI."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from libskylark_tpu import SketchContext
from libskylark_tpu.parallel import (
    ROWS,
    columnwise_sharded,
    columnwise_sharded_sparse,
    default_mesh,
    make_mesh,
    rowwise_sharded,
    rowwise_sharded_sparse,
    shard_rows,
)
from libskylark_tpu.sketch import CWT, JLT, SJLT, WZT
from libskylark_tpu.sketch import dense as dense_mod


@pytest.mark.slow
class TestShardMapSchedules:
    def test_rowwise_communication_free_matches_local(self, rng):
        n, s, m = 64, 16, 128
        A = jnp.asarray(rng.standard_normal((m, n)))
        mesh = default_mesh()
        S = JLT(n, s, SketchContext(seed=1))
        ref = S.apply(A, "rowwise")
        out = rowwise_sharded(S, shard_rows(A, mesh), mesh)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-10)

    def test_rowwise_hash_sketch(self, rng):
        n, s, m = 48, 12, 64
        A = jnp.asarray(rng.standard_normal((m, n)))
        mesh = default_mesh()
        S = CWT(n, s, SketchContext(seed=2))
        ref = S.apply(A, "rowwise")
        out = rowwise_sharded(S, shard_rows(A, mesh), mesh)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-10)

    def test_columnwise_psum_matches_local(self, rng):
        n, s, m = 128, 32, 24
        A = jnp.asarray(rng.standard_normal((n, m)))
        mesh = default_mesh()
        S = JLT(n, s, SketchContext(seed=3))
        ref = S.apply(A, "columnwise")
        out = columnwise_sharded(S, shard_rows(A, mesh), mesh)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-8, atol=1e-10
        )

    def test_columnwise_psum_scatter(self, rng):
        n, s, m = 64, 32, 8
        A = jnp.asarray(rng.standard_normal((n, m)))
        mesh = default_mesh()
        S = JLT(n, s, SketchContext(seed=4))
        ref = S.apply(A, "columnwise")
        out = columnwise_sharded(S, shard_rows(A, mesh), mesh, scatter=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-8, atol=1e-10
        )


def _random_bcoo(rng, shape, density=0.1):
    from jax.experimental import sparse as jsparse

    M = rng.standard_normal(shape) * (rng.random(shape) < density)
    return jsparse.BCOO.fromdense(jnp.asarray(M)), M


def test_capacity_suggestion_rejects_2d_mesh(rng):
    """The 1-D capacity helper's n/p row blocks don't match the 2-D
    grid's row-axis exchange — a silently wrong capacity would drop
    entries, so multi-axis meshes must be refused loudly."""
    from jax.sharding import Mesh

    from libskylark_tpu.parallel import suggest_sparse_out_capacity

    S = CWT(32, 16, SketchContext(seed=43))
    A, _ = _random_bcoo(rng, (32, 6), density=0.3)
    devs = np.array(jax.devices())
    with pytest.raises(ValueError, match="1-D only"):
        suggest_sparse_out_capacity(
            S, A, Mesh(devs.reshape(4, 2), ("r", "c"))
        )
    assert suggest_sparse_out_capacity(S, A, Mesh(devs, (ROWS,))) >= 1


@pytest.mark.slow
class TestSparseShardedSchedules:
    """P6: sharded sparse hash sketches must equal the single-device BCOO
    apply (same counter windows → same buckets/values, only the schedule
    differs)."""

    @pytest.mark.parametrize(
        "sketch_cls,kw", [(CWT, {"nnz": 1}), (SJLT, {"nnz": 4}), (WZT, {"p": 1.5})]
    )
    def test_columnwise_psum(self, rng, sketch_cls, kw):
        n, s, m = 128, 16, 24
        A, _ = _random_bcoo(rng, (n, m))
        mesh = default_mesh()
        S = sketch_cls(n, s, SketchContext(seed=5), **kw)
        ref = S.apply(A, "columnwise").todense()
        out = columnwise_sharded_sparse(S, A, mesh)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-8, atol=1e-10
        )

    def test_columnwise_psum_scatter(self, rng):
        n, s, m = 64, 32, 8
        A, _ = _random_bcoo(rng, (n, m))
        mesh = default_mesh()
        S = CWT(n, s, SketchContext(seed=6))
        ref = S.apply(A, "columnwise").todense()
        out = columnwise_sharded_sparse(S, A, mesh, scatter=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-8, atol=1e-10
        )

    def test_rowwise_communication_free(self, rng):
        n, s, m = 96, 12, 64
        A, _ = _random_bcoo(rng, (m, n))
        mesh = default_mesh()
        S = CWT(n, s, SketchContext(seed=7))
        ref = S.apply(A, "rowwise").todense()
        out = rowwise_sharded_sparse(S, A, mesh)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-8, atol=1e-10
        )

    def test_ragged_row_blocks(self, rng):
        # skew all nonzeros into the first row block: padding must stay
        # harmless and the result exact
        from jax.experimental import sparse as jsparse

        n, s, m = 64, 8, 8
        M = np.zeros((n, m))
        M[: n // 8] = rng.standard_normal((n // 8, m))
        A = jsparse.BCOO.fromdense(jnp.asarray(M))
        mesh = default_mesh()
        S = CWT(n, s, SketchContext(seed=8))
        ref = S.apply(A, "columnwise").todense()
        out = columnwise_sharded_sparse(S, A, mesh)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-8, atol=1e-10
        )

    def test_shape_validation(self, rng):
        A, _ = _random_bcoo(rng, (60, 8))
        mesh = default_mesh()
        S = CWT(64, 8, SketchContext(seed=9))
        with pytest.raises(ValueError):
            columnwise_sharded_sparse(S, A, mesh)  # wrong N
        S2 = CWT(60, 8, SketchContext(seed=10))
        with pytest.raises(ValueError):
            columnwise_sharded_sparse(S2, A, mesh)  # 60 % 8 != 0


@pytest.mark.slow
class TestSparseOutSchedules:
    """SURVEY row 65 (SpParMat → SpParMat, ``hash_transform_CombBLAS.hpp:
    136-302``): sharded sparse sketches whose OUTPUT stays sparse and
    sharded — columnwise routes relabeled entries to their output-row
    owner through one fixed-capacity all_to_all exchange; rowwise is
    communication-free.  Parity target: the local BCOO→BCOO apply."""

    @pytest.mark.parametrize(
        "sketch_cls,kw", [(CWT, {}), (SJLT, {"nnz": 3}), (WZT, {})]
    )
    def test_columnwise_matches_local(self, rng, sketch_cls, kw):
        from libskylark_tpu.parallel import columnwise_sharded_sparse_out

        n, s, m = 64, 40, 12
        mesh = default_mesh()
        S = sketch_cls(n, s, SketchContext(seed=41), **kw)
        A, _ = _random_bcoo(rng, (n, m), density=0.3)
        out = columnwise_sharded_sparse_out(S, A, mesh)
        ref = S.apply(A, "columnwise")
        np.testing.assert_allclose(
            np.asarray(out.todense()), np.asarray(ref.todense()),
            rtol=1e-5, atol=1e-5,
        )

    def test_columnwise_to_bcoo_stays_sparse(self, rng):
        from libskylark_tpu.parallel import columnwise_sharded_sparse_out

        n, s, m = 64, 4096, 8  # output (4096, 8): dense merge would be 32k
        mesh = default_mesh()
        S = CWT(n, s, SketchContext(seed=42))
        A, _ = _random_bcoo(rng, (n, m), density=0.2)
        out = columnwise_sharded_sparse_out(S, A, mesh)
        # Per-shard storage is entry-proportional, never (S, m):
        p = mesh.size
        assert out.data.shape[1] <= p * S.nnz * max(1, A.nse)
        bc = out.to_bcoo()
        assert bc.shape == (s, m)
        # ≤ one output entry per input nonzero (dedup can only shrink)
        assert bc.nse <= S.nnz * A.nse + 1


    def test_rowwise_matches_local(self, rng):
        from libskylark_tpu.parallel import rowwise_sharded_sparse_out

        n, s, m = 96, 24, 64
        mesh = default_mesh()
        for S in (
            CWT(n, s, SketchContext(seed=43)),
            SJLT(n, s, SketchContext(seed=44), nnz=2),
        ):
            A, _ = _random_bcoo(rng, (m, n), density=0.25)
            out = rowwise_sharded_sparse_out(S, A, mesh)
            ref = S.apply(A, "rowwise")
            np.testing.assert_allclose(
                np.asarray(out.todense()), np.asarray(ref.todense()),
                rtol=1e-5, atol=1e-5,
            )

    def test_columnwise_shape_validation(self, rng):
        from libskylark_tpu.parallel import columnwise_sharded_sparse_out

        mesh = default_mesh()
        A, _ = _random_bcoo(rng, (64, 8))
        S = CWT(64, 12, SketchContext(seed=45))  # 12 % 8 != 0
        with pytest.raises(ValueError, match="divisible"):
            columnwise_sharded_sparse_out(S, A, mesh)

    @pytest.mark.parametrize(
        "sketch_cls,kw", [(CWT, {}), (SJLT, {"nnz": 3})]
    )
    def test_2d_grid_matches_local(self, rng, sketch_cls, kw):
        """Full SpParMat→SpParMat: input on a (4, 2) grid, output on the
        SAME grid, routing column-local over the mesh row axis."""
        from libskylark_tpu.parallel import (
            columnwise_sharded_sparse_out_2d,
            make_mesh,
        )

        mesh = make_mesh((4, 2), ("r", "c"))
        n, s, m = 32, 16, 10
        S = sketch_cls(n, s, SketchContext(seed=61), **kw)
        A, _ = _random_bcoo(rng, (n, m), density=0.35)
        out = columnwise_sharded_sparse_out_2d(S, A, mesh)
        assert out.col_block == m // 2
        ref = S.apply(A, "columnwise")
        np.testing.assert_allclose(
            np.asarray(out.todense()), np.asarray(ref.todense()),
            rtol=1e-5, atol=1e-5,
        )

    def test_property_sweep_random_configs(self, rng):
        """Randomized property sweep: shapes, densities, sketch types,
        and capacity choices drawn per round; parity vs the local BCOO
        apply must hold for every draw (edge shards, hot buckets, and
        sparse corners appear naturally across draws)."""
        from libskylark_tpu.parallel import (
            columnwise_sharded_sparse_out,
            suggest_sparse_out_capacity,
        )

        mesh = default_mesh()
        p = mesh.size
        # The capacity helper is strictly 1-D (it refuses multi-axis
        # meshes); the 1-D schedule flattens the 2-D default mesh to p
        # devices, so a flat p-device mesh gives the matching count.
        flat = make_mesh((p,), (ROWS,))
        for trial in range(6):
            n = p * int(rng.integers(2, 9))
            m = int(rng.integers(1, 14))
            s = p * int(rng.integers(1, 7))
            density = float(rng.uniform(0.05, 0.9))
            cls, kw = [(CWT, {}), (SJLT, {"nnz": 2}), (WZT, {})][trial % 3]
            S = cls(n, s, SketchContext(seed=100 + trial), **kw)
            A, _ = _random_bcoo(rng, (n, m), density=density)
            if trial % 2 == 0:
                # Half the trials run f32: the bitcast single-exchange
                # lane of _exchange_entries is otherwise invisible under
                # the suite's forced x64 (the known f32-parity trap).
                from jax.experimental import sparse as jsparse

                A = jsparse.BCOO(
                    (A.data.astype(jnp.float32), A.indices), shape=A.shape
                )
            cap = (
                None if trial % 2
                else suggest_sparse_out_capacity(S, A, flat)
            )
            out = columnwise_sharded_sparse_out(S, A, mesh, capacity=cap)
            ref = S.apply(A, "columnwise")
            np.testing.assert_allclose(
                np.asarray(out.todense()), np.asarray(ref.todense()),
                rtol=1e-5, atol=1e-5,
                err_msg=f"trial={trial} n={n} m={m} s={s} "
                        f"density={density:.2f} cap={cap}",
            )

    def test_empty_matrix(self, rng):
        """nse=0 input: all shards hold only padding; the result is the
        all-zero sketch (and to_bcoo's empty-keep path)."""
        from jax.experimental import sparse as jsparse

        from libskylark_tpu.parallel import columnwise_sharded_sparse_out

        mesh = default_mesh()
        n, s, m = 32, 16, 4
        A = jsparse.BCOO.fromdense(jnp.zeros((n, m), jnp.float32), nse=1)
        S = CWT(n, s, SketchContext(seed=48))
        out = columnwise_sharded_sparse_out(S, A, mesh)
        np.testing.assert_array_equal(
            np.asarray(out.todense()), np.zeros((s, m), np.float32)
        )

    def test_chain_device_resident(self, rng):
        """S2·(S1·A) chained on-device: the sharded result's per-shard
        entry arrays feed the next schedule directly — no host exit, no
        densification in between.  Both the dense-merge and sparse-out
        second hops must match the local chain (duplicates are fine:
        hashing is linear in entries)."""
        from libskylark_tpu.parallel import columnwise_sharded_sparse_out

        mesh = default_mesh()
        n, m, s1, s2 = 64, 10, 40, 16
        S1 = CWT(n, s1, SketchContext(seed=71))
        S2 = SJLT(s1, s2, SketchContext(seed=72), nnz=2)
        A, _ = _random_bcoo(rng, (n, m), density=0.3)
        mid = columnwise_sharded_sparse_out(S1, A, mesh)
        ref = np.asarray(
            S2.apply(S1.apply(A, "columnwise"), "columnwise").todense()
        )
        dense_chain = mid.sketch_columnwise(S2, dense_output=True)
        np.testing.assert_allclose(
            np.asarray(dense_chain), ref, rtol=1e-5, atol=1e-5
        )
        sparse_chain = mid.sketch_columnwise(S2, dense_output=False)
        np.testing.assert_allclose(
            np.asarray(sparse_chain.todense()), ref, rtol=1e-5, atol=1e-5
        )
        # Validation: wrong inner dimension, non-divisible scatter, and
        # 2-D-grid sources all raise cleanly.
        with pytest.raises(ValueError, match="S2.n"):
            mid.sketch_columnwise(CWT(s1 + 8, 8, SketchContext(seed=73)))
        with pytest.raises(ValueError, match="divisible"):
            mid.sketch_columnwise(
                CWT(s1, 12, SketchContext(seed=74)), scatter=True
            )
        from libskylark_tpu.parallel import (
            columnwise_sharded_sparse_out_2d,
            make_mesh,
        )

        grid = make_mesh((4, 2), ("r", "c"))
        mid2d = columnwise_sharded_sparse_out_2d(
            CWT(n, 16, SketchContext(seed=75)), A, grid
        )
        with pytest.raises(ValueError, match="2-D grid"):
            mid2d.sketch_columnwise(CWT(16, 8, SketchContext(seed=76)))

    def test_2d_grid_needs_2d_mesh(self, rng):
        from libskylark_tpu.parallel import (
            columnwise_sharded_sparse_out_2d,
            make_mesh,
        )

        mesh = make_mesh((8,), ("p",))  # 1-axis: must be rejected
        A, _ = _random_bcoo(rng, (64, 8))
        S = CWT(64, 16, SketchContext(seed=62))
        with pytest.raises(ValueError, match="2-axis"):
            columnwise_sharded_sparse_out_2d(S, A, mesh)

    def test_safe_capacity_never_drops_on_hot_bucket(self, rng):
        """Adversarial: a sketch where EVERY input row hashes to a
        bucket owned by ONE shard must survive the default capacity
        (all entries of one source to one destination).  The
        concentration is constructed, not seed-hunted — a uniform hash
        never concentrates 32 rows on one of 8 owners by chance."""
        from libskylark_tpu.parallel import columnwise_sharded_sparse_out

        class HotCWT(CWT):
            """Every coordinate hashes to bucket 1 (owner shard 0)."""

            def buckets(self, start=0, num=None):
                base = super().buckets(start=start, num=num)
                return jnp.ones_like(base)

        n, s, m = 32, 16, 4
        mesh = default_mesh()
        S = HotCWT(n, s, SketchContext(seed=46))
        A, _ = _random_bcoo(rng, (n, m), density=0.9)
        out = columnwise_sharded_sparse_out(S, A, mesh)
        ref = S.apply(A, "columnwise")  # local path uses the same override
        np.testing.assert_allclose(
            np.asarray(out.todense()), np.asarray(ref.todense()),
            rtol=1e-5, atol=1e-5,
        )

    def test_tight_capacity_ignores_padding(self, rng):
        """Padding entries ride the sentinel destination, so a capacity
        equal to the true max per-(src, dst) REAL entry count loses
        nothing even when shards are skewed (some heavily padded)."""
        from libskylark_tpu.parallel import columnwise_sharded_sparse_out

        n, s, m = 64, 16, 6
        mesh = default_mesh()
        p = mesh.size
        # Skewed rows: all nonzeros in the first row block.
        M = np.zeros((n, m))
        M[: n // p] = rng.standard_normal((n // p, m))
        from jax.experimental import sparse as jsparse

        A = jsparse.BCOO.fromdense(jnp.asarray(M, jnp.float32))
        S = CWT(n, s, SketchContext(seed=47))
        from libskylark_tpu.parallel import suggest_sparse_out_capacity

        # helper is 1-D only; the flat mesh matches the flattened schedule
        need = suggest_sparse_out_capacity(S, A, make_mesh((p,), (ROWS,)))
        # Tight: with one hot source block and a near-uniform hash over
        # p destinations, the exact count sits near nse/p — far under
        # the drop-proof default of nnz*nse.
        assert need < S.nnz * A.nse // 2
        out = columnwise_sharded_sparse_out(S, A, mesh, capacity=need)
        ref = S.apply(A, "columnwise")
        np.testing.assert_allclose(
            np.asarray(out.todense()), np.asarray(ref.todense()),
            rtol=1e-5, atol=1e-5,
        )


@pytest.mark.slow
class TestSparse2DGrid:
    """P6 2-D option (≙ hash_transform_CombBLAS's √p×√p grid): nonzeros
    owned by (row-block, col-block); per-shard local (S, m/pc)
    accumulators, one psum over the mesh ROW axis, output col-sharded."""

    @pytest.mark.parametrize(
        "sketch_cls,kw", [(CWT, {}), (SJLT, {"nnz": 3}), (WZT, {"p": 1.5})]
    )
    def test_matches_local(self, rng, sketch_cls, kw):
        from libskylark_tpu.parallel import columnwise_sharded_sparse_2d

        n, m, s = 128, 32, 16
        A, _ = _random_bcoo(rng, (n, m), density=0.15)
        mesh = default_mesh()  # ('rows', 'cols') = (2, 4)
        S = sketch_cls(n, s, SketchContext(seed=21), **kw)
        ref = S.apply(A, "columnwise").todense()
        out = columnwise_sharded_sparse_2d(S, A, mesh)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-8, atol=1e-10
        )

    def test_skewed_cells(self, rng):
        # All nonzeros in one grid cell: padding stays harmless.
        from jax.experimental import sparse as jsparse

        from libskylark_tpu.parallel import columnwise_sharded_sparse_2d

        n, m, s = 64, 16, 8
        M = np.zeros((n, m))
        M[:8, :2] = rng.standard_normal((8, 2))
        A = jsparse.BCOO.fromdense(jnp.asarray(M))
        mesh = default_mesh()
        S = CWT(n, s, SketchContext(seed=22))
        ref = S.apply(A, "columnwise").todense()
        out = columnwise_sharded_sparse_2d(S, A, mesh)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-8, atol=1e-10
        )

    def test_needs_2d_mesh(self, rng):
        from libskylark_tpu.parallel import (
            columnwise_sharded_sparse_2d,
            make_mesh,
        )

        A, _ = _random_bcoo(rng, (64, 16))
        S = CWT(64, 8, SketchContext(seed=23))
        with pytest.raises(ValueError, match="2-axis"):
            columnwise_sharded_sparse_2d(S, A, make_mesh((8,), ("rows",)))

    def test_exactly_one_allreduce_over_rows(self, rng):
        """Schedule lock: the merge is ONE all-reduce (over the mesh row
        axis only); nothing else communicates."""
        from libskylark_tpu.parallel.collectives import (
            _columnwise_sparse_2d_program,
            _shard_coo_grid,
        )

        n, m, s = 128, 32, 16
        A, _ = _random_bcoo(rng, (n, m), density=0.15)
        mesh = default_mesh()
        pr, pc = mesh.shape["rows"], mesh.shape["cols"]
        S = CWT(n, s, SketchContext(seed=24))
        d, lr, lc = _shard_coo_grid(A, pr, pc, n // pr, m // pc)
        counts = _collective_counts(
            _columnwise_sparse_2d_program(S, n // pr, m // pc, mesh),
            d, lr, lc,
        )
        assert counts == {"all-reduce": 1}, counts


_COLLECTIVE_RE = __import__("re").compile(
    r"\b(all-reduce|reduce-scatter|all-gather|all-to-all|"
    r"collective-permute)(?:-start)?\("
)


def _collective_counts(fn, *args):
    """Counts of collective instructions in the fully compiled HLO."""
    from collections import Counter

    txt = jax.jit(fn).lower(*args).compile().as_text()
    return Counter(m.group(1) for m in _COLLECTIVE_RE.finditer(txt))


class TestCompiledCommunicationSchedules:
    """P2/P5/P6 are *schedule* invariants, not just value invariants: the
    reference documents rowwise sketch-apply as communication-free and
    columnwise as one reduction (``doc/sphinx/sketching.rst:104-118``).
    Value-parity tests can't catch a JAX/XLA upgrade or refactor that
    silently starts communicating, so these assert collective-op counts
    in the compiled HLO itself (VERDICT round 2 item 4)."""

    def test_rowwise_dense_zero_collectives(self, rng):
        n, s, m = 64, 16, 128
        mesh = default_mesh()
        S = JLT(n, s, SketchContext(seed=31))
        A = shard_rows(jnp.asarray(rng.standard_normal((m, n))), mesh)
        counts = _collective_counts(lambda a: rowwise_sharded(S, a, mesh), A)
        assert not counts, f"rowwise schedule must be comm-free, got {counts}"

    def test_rowwise_hash_zero_collectives(self, rng):
        n, s, m = 48, 12, 64
        mesh = default_mesh()
        S = CWT(n, s, SketchContext(seed=32))
        A = shard_rows(jnp.asarray(rng.standard_normal((m, n))), mesh)
        counts = _collective_counts(lambda a: rowwise_sharded(S, a, mesh), A)
        assert not counts, f"rowwise schedule must be comm-free, got {counts}"

    def test_columnwise_exactly_one_allreduce(self, rng):
        n, s, m = 128, 32, 24
        mesh = default_mesh()
        S = JLT(n, s, SketchContext(seed=33))
        A = shard_rows(jnp.asarray(rng.standard_normal((n, m))), mesh)
        counts = _collective_counts(
            lambda a: columnwise_sharded(S, a, mesh), A
        )
        assert counts == {"all-reduce": 1}, counts

    def test_columnwise_scatter_exactly_one_reduce_scatter(self, rng):
        n, s, m = 64, 32, 8
        mesh = default_mesh()
        S = JLT(n, s, SketchContext(seed=34))
        A = shard_rows(jnp.asarray(rng.standard_normal((n, m))), mesh)
        counts = _collective_counts(
            lambda a: columnwise_sharded(S, a, mesh, scatter=True), A
        )
        assert counts == {"reduce-scatter": 1}, counts

    @staticmethod
    def _split_coo(A, mesh, block):
        from libskylark_tpu.parallel.collectives import _shard_coo_rows

        return _shard_coo_rows(A, mesh.size, block)

    @pytest.mark.slow
    def test_sparse_rowwise_zero_collectives(self, rng):
        from libskylark_tpu.parallel.collectives import _rowwise_sparse_program

        n, s, m = 96, 12, 64
        mesh = default_mesh()
        S = CWT(n, s, SketchContext(seed=35))
        A, _ = _random_bcoo(rng, (m, n))
        # The COO row-block split is host-side; the device program (the
        # part a schedule regression could infect) is lowered directly.
        d, lr, cc = self._split_coo(A, mesh, m // mesh.size)
        counts = _collective_counts(_rowwise_sparse_program(S, m // mesh.size, mesh), d, lr, cc)
        assert not counts, f"sparse rowwise must be comm-free, got {counts}"

    def test_sparse_columnwise_exactly_one_allreduce(self, rng):
        from libskylark_tpu.parallel.collectives import (
            _columnwise_sparse_program,
        )

        n, s, m = 128, 16, 24
        mesh = default_mesh()
        S = SJLT(n, s, SketchContext(seed=36), nnz=4)
        A, _ = _random_bcoo(rng, (n, m))
        d, lr, cc = self._split_coo(A, mesh, n // mesh.size)
        counts = _collective_counts(
            _columnwise_sparse_program(S, m, n // mesh.size, mesh, False),
            d, lr, cc,
        )
        assert counts == {"all-reduce": 1}, counts

    @pytest.mark.slow
    def test_sparse_columnwise_scatter_one_reduce_scatter(self, rng):
        from libskylark_tpu.parallel.collectives import (
            _columnwise_sparse_program,
        )

        n, s, m = 64, 32, 8
        mesh = default_mesh()
        S = CWT(n, s, SketchContext(seed=37))
        A, _ = _random_bcoo(rng, (n, m))
        d, lr, cc = self._split_coo(A, mesh, n // mesh.size)
        counts = _collective_counts(
            _columnwise_sparse_program(S, m, n // mesh.size, mesh, True),
            d, lr, cc,
        )
        assert counts == {"reduce-scatter": 1}, counts

    @pytest.mark.slow
    @pytest.mark.parametrize("dtype,want", [(jnp.float32, 1), (jnp.float64, 2)])
    def test_sparse_out_columnwise_all_to_all_only(self, rng, dtype, want):
        """The sparse→sparse columnwise schedule is an entry EXCHANGE:
        f32 rides ONE packed all-to-all (values bitcast into the index
        buffer), f64 two (values + packed indices); no reduction
        collective, and — the row-65 point — no (S, m) dense
        accumulator anywhere in the program."""
        from jax.experimental import sparse as jsparse

        from libskylark_tpu.parallel.collectives import (
            _columnwise_sparse_out_program,
        )

        n, s, m = 64, 40, 12
        mesh = default_mesh()
        S = SJLT(n, s, SketchContext(seed=38), nnz=3)
        M = rng.standard_normal((n, m)) * (rng.random((n, m)) < 0.3)
        A = jsparse.BCOO.fromdense(jnp.asarray(M, dtype))
        block = n // mesh.size
        d, lr, cc = self._split_coo(A, mesh, block)
        cap = S.nnz * d.shape[1]
        counts = _collective_counts(
            _columnwise_sparse_out_program(
                S, block, s // mesh.size, cap, mesh
            ),
            d, lr, cc,
        )
        assert counts == {"all-to-all": want}, counts

    @pytest.mark.slow
    def test_sparse_out_2d_one_row_axis_all_to_all(self, rng):
        """The 2-D sparse-out exchange rides the mesh ROW axis only:
        one all-to-all (f32), no reduction collective, no dense block."""
        from jax.experimental import sparse as jsparse

        from libskylark_tpu.parallel import make_mesh
        from libskylark_tpu.parallel.collectives import (
            _columnwise_sparse_out_2d_program,
            _shard_coo_grid,
        )

        n, s, m = 32, 16, 10
        mesh = make_mesh((4, 2), ("r", "c"))
        S = CWT(n, s, SketchContext(seed=63))
        M = rng.standard_normal((n, m)) * (rng.random((n, m)) < 0.35)
        A = jsparse.BCOO.fromdense(jnp.asarray(M, jnp.float32))
        d, lr, lc = _shard_coo_grid(A, 4, 2, n // 4, m // 2)
        cap = S.nnz * d.shape[2]
        counts = _collective_counts(
            _columnwise_sparse_out_2d_program(S, n // 4, s // 4, cap, mesh),
            d, lr, lc,
        )
        assert counts == {"all-to-all": 1}, counts

    @pytest.mark.slow
    def test_sparse_out_rowwise_zero_collectives(self, rng):
        from libskylark_tpu.parallel.collectives import (
            _rowwise_sparse_out_program,
        )

        n, s, m = 96, 24, 64
        mesh = default_mesh()
        S = CWT(n, s, SketchContext(seed=39))
        A, _ = _random_bcoo(rng, (m, n), density=0.25)
        d, lr, cc = self._split_coo(A, mesh, m // mesh.size)
        counts = _collective_counts(
            _rowwise_sparse_out_program(S, mesh), d, lr, cc
        )
        assert not counts, f"sparse-out rowwise must be comm-free, got {counts}"

    def test_traced_start_requires_num(self):
        S = CWT(64, 8, SketchContext(seed=11))
        with pytest.raises(ValueError, match="num is required"):
            jax.jit(lambda o: S.buckets(start=o))(jnp.uint32(3))


class TestPanelBlockedApply:
    @pytest.mark.slow
    def test_blocked_matches_unblocked(self, rng, monkeypatch):
        n, s, m = 250, 32, 10  # 250 % panel != 0 -> exercises the remainder
        A = jnp.asarray(rng.standard_normal((n, m)))
        S = JLT(n, s, SketchContext(seed=5))
        ref = S.apply(A, "columnwise")
        ref_r = S.apply(A.T, "rowwise")  # references BEFORE forcing panels
        monkeypatch.setattr(dense_mod, "MAX_REALIZE_ELEMENTS", 1024)
        out = S.apply(A, "columnwise")
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-9, atol=1e-11
        )
        out_r = S.apply(A.T, "rowwise")
        np.testing.assert_allclose(
            np.asarray(out_r), np.asarray(ref_r), rtol=1e-9, atol=1e-11
        )

    @pytest.mark.slow
    def test_sparse_over_threshold_raises(self, rng, monkeypatch):
        from jax.experimental import sparse as jsparse

        from libskylark_tpu.utils.exceptions import UnsupportedError

        monkeypatch.setattr(dense_mod, "MAX_REALIZE_ELEMENTS", 64)
        S = JLT(32, 8, SketchContext(seed=7))
        A = jsparse.BCOO.fromdense(jnp.eye(32))
        with pytest.raises(UnsupportedError, match="CWT"):
            S.apply(A, "columnwise")

    def test_traced_offset_window_crosses_2_32(self):
        # window_bits with base near 2^32: traced vs concrete offsets must
        # agree bit-for-bit (the carry path).
        from libskylark_tpu.core.random import window_bits

        base = (1 << 32) - 64
        hi_c, lo_c = window_bits(5, base, 1000, 0, 40, 3, 50)
        off = jnp.asarray(40, jnp.uint32)
        hi_t, lo_t = jax.jit(
            lambda o: window_bits(5, base, 1000, 0, o, 3, 50)
        )(off)
        np.testing.assert_array_equal(np.asarray(hi_c), np.asarray(hi_t))
        np.testing.assert_array_equal(np.asarray(lo_c), np.asarray(lo_t))

    def test_blocked_jittable(self, rng, monkeypatch):
        monkeypatch.setattr(dense_mod, "MAX_REALIZE_ELEMENTS", 512)
        S = JLT(100, 16, SketchContext(seed=6))
        A = jnp.asarray(rng.standard_normal((100, 4)))
        out = jax.jit(lambda X: S.apply(X, "columnwise"))(A)
        assert out.shape == (16, 4)


class TestLinearCLI:
    @pytest.mark.slow
    def test_solves(self, tmp_path, rng, capsys):
        from libskylark_tpu.cli.linear import main
        from libskylark_tpu.io import write_libsvm

        A = rng.standard_normal((500, 10))
        x_true = rng.standard_normal(10)
        b = A @ x_true
        write_libsvm(tmp_path / "p", A, b)
        rc = main([str(tmp_path / "p"), "--solution", str(tmp_path / "x.npy")])
        assert rc == 0
        x = np.load(tmp_path / "x.npy")
        np.testing.assert_allclose(x, x_true, rtol=1e-4, atol=1e-6)


class TestStreamingKrrCommSchedule:
    """HLO lock for the sharded streaming-KRR chunk programs' comm
    structure.  Two load-bearing properties:
    (1) XLA hoists the per-panel partial-contraction psums OUT of the
    panel while-loop (one all-reduce per program, not nb); (2) the
    traced-offset dynamic_slice of the row-sharded residual costs
    all-gathers of R — known and bounded.  A JAX upgrade that regresses
    either changes these counts."""

    def _programs(self):
        from libskylark_tpu.ml import GaussianKernel, KrrParams
        from libskylark_tpu.ml.krr import (
            _chunk_sizes,
            _tag,
            streaming_krr_chunk_programs,
        )
        from libskylark_tpu.parallel import constrain_rows

        mesh = default_mesh()
        N, D, S, BR, T = 64 * mesh.size, 16, 8, 16 * mesh.size, 1
        kernel = GaussianKernel(D, sigma=2.0)
        params = KrrParams(max_split=0)
        sizes = _chunk_sizes(D, S, params)
        maps = [
            kernel.create_rft(sz, _tag(params), SketchContext(seed=72))
            for sz in sizes
        ]

        def block_fn(start, rows):
            base = jax.lax.broadcasted_iota(jnp.float32, (rows, D), 0)
            return constrain_rows(base * 1e-3, mesh)

        progs = streaming_krr_chunk_programs(
            maps, 0, N // BR, BR, block_fn, jnp.float32
        )
        from jax.sharding import NamedSharding, PartitionSpec as P

        row_sh = NamedSharding(mesh, P(None, mesh.axis_names[0], None))
        rep_sh = NamedSharding(mesh, P())
        R = jax.ShapeDtypeStruct(
            (N // BR, BR, T), jnp.float32, sharding=row_sh
        )
        W = jax.ShapeDtypeStruct((sizes[0], T), jnp.float32, sharding=rep_sh)
        lam = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep_sh)
        return progs, lam, R, W

    @staticmethod
    def _counts(jitted, *specs):
        from collections import Counter

        txt = jitted.lower(*specs).compile().as_text()
        return Counter(m.group(1) for m in _COLLECTIVE_RE.finditer(txt))

    def test_gram_one_allreduce_hoisted(self):
        (gram, _, _), lam, R, W = self._programs()
        counts = self._counts(gram, lam)
        assert counts == {"all-reduce": 1}, counts

    def test_zr_schedule(self):
        """Panel-major R (round 4): the traced-index panel slice stays
        off the sharded axis, so zr's only collective is the hoisted
        partial-contraction psum — the R all-gather is GONE."""
        (_, zr, _), lam, R, W = self._programs()
        counts = self._counts(zr, lam, R, W)
        assert counts == {"all-reduce": 1}, counts

    def test_apply_delta_schedule(self):
        (_, _, apply_delta), lam, R, W = self._programs()
        counts = self._counts(apply_delta, R, W)
        assert not counts, counts
