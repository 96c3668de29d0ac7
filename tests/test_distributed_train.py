"""Distributed kernel-machine training tests (``train`` marker — tier-1,
per-test timeout via conftest).

The load-bearing guarantees of ``ml/distributed.py``:

- world=1 distributed training is BIT-FOR-BIT identical to the
  in-process ``BlockADMMSolver.train`` (streamed rowwise-bucketed
  feature materialization == ``_prepare``'s columnwise apply, and the
  iteration runs as one fused jit when no collective crosses it);
- a run interrupted mid-stream or mid-training and resumed reproduces
  the uninterrupted model bit-for-bit (the real-SIGKILL multi-process
  variant rides ``test_distributed.py``'s slow tier via
  ``_elastic_child.py``'s ``ELASTIC_TRAIN=1`` mode);
- simulated 2-rank consensus merging computes rank-identical global
  leaves and matches the unsharded solver to f32 accumulation accuracy;
- a resume under a changed partition fails fast with code 109;
- a guard chunk-sentinel trip mid-stream replays the chunk and the
  trained model still matches the clean run bit-for-bit;
- trained models round-trip through the serve registry dtype-faithfully.
"""

import os

import jax.numpy as jnp
import numpy as np
import pytest
from _builds import builds

from libskylark_tpu import SketchContext
from libskylark_tpu.ml import ADMMParams, BlockADMMSolver, admm
from libskylark_tpu.ml.distributed import (
    DistributedBlockADMMTrainer,
    prepare_rank_admm,
    rank_chunked_solver,
    stream_feature_blocks,
    validate_train_partition,
)
from libskylark_tpu.ml.kernels import GaussianKernel
from libskylark_tpu.resilient import FaultPlan, SimulatedPreemption
from libskylark_tpu.streaming import ElasticParams, RowPartition
from libskylark_tpu.utils.exceptions import (
    InvalidParameters,
    WorldMismatchError,
)

pytestmark = pytest.mark.train

N, D_IN, BATCH = 32, 4, 4


def bits(x):
    return np.asarray(x).tobytes()


def make_data():
    rng = np.random.default_rng(7)
    X = rng.standard_normal((N, D_IN))
    y = np.array([1.0, 2.0] * (N // 2))
    return X, y


def make_maps(seed=11, per_map=32):
    kern = GaussianKernel(D_IN, 2.0)
    ctx = SketchContext(seed=seed)
    return [kern.create_rft(per_map, "regular", ctx) for _ in range(2)]


def make_params(**kw):
    kw.setdefault("rho", 1.0)
    kw.setdefault("lam", 0.01)
    kw.setdefault("maxiter", 8)
    kw.setdefault("data_partitions", 2)
    return ADMMParams(**kw)


def source_of(X, y, part):
    def factory(start):
        def it():
            for b in range(start, part.num_batches):
                lo = b * part.batch_rows
                hi = min(lo + part.batch_rows, part.nrows)
                yield X[lo:hi], y[lo:hi]
        return it()
    return factory


# ---------------------------------------------------------------------------
# partition validation: whole ADMM partitions per rank
# ---------------------------------------------------------------------------


class TestPartitionValidation:
    def test_aligned_partition_accepted(self):
        part = RowPartition(nrows=N, batch_rows=BATCH, world_size=1)
        assert validate_train_partition(part, 2) == N // 2

    def test_rows_not_divisible_rejected(self):
        part = RowPartition(nrows=N, batch_rows=BATCH, world_size=1)
        with pytest.raises(InvalidParameters):
            validate_train_partition(part, 5)

    def test_partition_split_across_ranks_rejected(self):
        # world=2 halves the rows at 16; data_partitions=1 means the one
        # partition (32 rows) would straddle both ranks — no owner.
        part = RowPartition(nrows=N, batch_rows=BATCH, world_size=2)
        with pytest.raises(InvalidParameters):
            validate_train_partition(part, 1)

    def test_nonpositive_partitions_rejected(self):
        part = RowPartition(nrows=N, batch_rows=BATCH, world_size=1)
        with pytest.raises(InvalidParameters):
            validate_train_partition(part, 0)


# ---------------------------------------------------------------------------
# world=1 bitwise parity vs the in-process solver
# ---------------------------------------------------------------------------


class TestWorldOneParity:
    def _distributed(self, X, y, maps, params, *, regression, **train_kw):
        part = RowPartition(nrows=N, batch_rows=BATCH, world_size=1)
        trainer = DistributedBlockADMMTrainer(
            "squared", "l2", maps, params, ElasticParams(prefetch=0)
        )
        return trainer.train(
            source_of(X, y, part), part, regression=regression, **train_kw
        )

    def test_regression_bitwise(self):
        X, y = make_data()
        maps, params = make_maps(), make_params()
        m_ref = BlockADMMSolver("squared", "l2", maps, params).train(
            X, y, regression=True
        )
        m_dist, info = self._distributed(
            X, y, maps, params, regression=True
        )
        assert bits(m_ref.W) == bits(m_dist.W)
        assert m_ref.history == m_dist.history
        assert info["iters"] == params.maxiter

    def test_classification_bitwise(self):
        X, y = make_data()
        maps, params = make_maps(), make_params()
        m_ref = BlockADMMSolver("squared", "l2", maps, params).train(X, y)
        m_dist, _ = self._distributed(
            X, y, maps, params, regression=False
        )
        assert bits(m_ref.W) == bits(m_dist.W)
        np.testing.assert_array_equal(
            np.asarray(m_ref.classes, np.float64),
            np.asarray(m_dist.classes, np.float64),
        )

    def test_info_contract(self):
        X, y = make_data()
        m, info = self._distributed(
            X, y, make_maps(), make_params(), regression=True
        )
        assert info["world_size"] == 1 and info["rank"] == 0
        assert info["rows"] == N and info["data_partitions"] == 2
        assert info["features"] == 64 and info["blocks"] == 2
        # the recorded rung IS the dtype the model trained at
        assert info["precision"] == str(np.asarray(m.W).dtype)
        assert info["escalated"] is False
        assert info["policy"]["route"] == "admm"
        assert info["recovery"]["stage"] == "distributed_block_admm"
        assert info["consensus_residual"] >= 0.0

    def test_streamed_blocks_match_prepare_bitwise(self):
        # The substrate seam under the parity above: the rowwise bucketed
        # streamed materialization, repartitioned to the columnwise
        # layout, IS _prepare's realization.
        X, y = make_data()
        maps, params = make_maps(), make_params()
        part = RowPartition(nrows=N, batch_rows=BATCH, world_size=1)
        Z_rows, Y_rows, nb = stream_feature_blocks(
            source_of(X, y, part), maps, part, ElasticParams(prefetch=0),
            targets=1,
        )
        assert nb == part.num_batches
        run = BlockADMMSolver("squared", "l2", maps, params)._prepare(
            jnp.asarray(X), y, None, True
        )
        P = params.data_partitions
        ni = N // P
        for Z, Zp_ref in zip(Z_rows, run.Zs):
            Zp = Z.reshape(P, ni, Z.shape[1]).transpose(0, 2, 1)
            assert bits(Zp) == bits(Zp_ref)


# ---------------------------------------------------------------------------
# the chunked-solver contract of ml/admm.py (pinned per its docstring)
# ---------------------------------------------------------------------------


class TestChunkedContract:
    def test_chunked_kill_resume_matches_train_bitwise(self, tmp_path):
        """``chunked()`` killed at a chunk boundary and resumed must
        reproduce not just the uninterrupted chunked run but ``train()``
        itself, bit-for-bit — the contract the distributed trainer's
        per-rank loop inherits."""
        from libskylark_tpu.resilient import ResilientParams, ResilientRunner

        X, y = make_data()
        maps, params = make_maps(), make_params()
        m_train = BlockADMMSolver("squared", "l2", maps, params).train(
            X, y, regression=True
        )

        def run(plan=None, resume=False):
            return ResilientRunner(
                BlockADMMSolver("squared", "l2", maps, params).chunked(
                    X, y, regression=True
                ),
                ResilientParams(
                    checkpoint_dir=str(tmp_path / "ck"),
                    checkpoint_every=3, resume=resume,
                ),
                fault_plan=plan,
            ).run()

        with pytest.raises(SimulatedPreemption):
            run(plan=FaultPlan(preempt_after_chunk=0))
        m_res = run(resume=True)
        assert bits(m_train.W) == bits(m_res.W)
        np.testing.assert_array_equal(m_train.history, m_res.history)


# ---------------------------------------------------------------------------
# simulated 2-rank consensus: rank-identical, matches unsharded
# ---------------------------------------------------------------------------


class TestSimulatedTwoRank:
    def test_consensus_merge_matches_unsharded(self):
        X, y = make_data()
        maps, params = make_maps(), make_params()
        m_ref = BlockADMMSolver("squared", "l2", maps, params).train(
            X, y, regression=True
        )
        part = RowPartition(nrows=N, batch_rows=BATCH, world_size=2)
        src = source_of(X, y, part)

        preps = []
        for r in (0, 1):
            ep = ElasticParams(rank=r, world_size=2, prefetch=0)
            Z_rows, Y_rows, _ = stream_feature_blocks(
                src, maps, part, ep, targets=1
            )
            preps.append(
                prepare_rank_admm(
                    "squared", "l2", maps, params, part, r, Z_rows,
                    Y_rows, regression=True,
                )
            )

        # Lockstep split schedule with the psum merged by hand — the
        # exact programs a real 2-process world runs.
        states = [p.state0 for p in preps]
        hist = [[], []]
        for _ in range(params.maxiter):
            outs = [
                admm.admm_local(p.spec, st, *p.operands)
                for p, st in zip(preps, states)
            ]
            wi_g = np.asarray(outs[0][1]) + np.asarray(outs[1][1])
            obj_g = np.asarray(outs[0][2]) + np.asarray(outs[1][2])
            for r in (0, 1):
                states[r] = admm.admm_merge(
                    preps[r].spec, outs[r][0], jnp.asarray(wi_g),
                    jnp.asarray(obj_g)
                )
                hist[r].append(float(states[r][-1]))

        # Global consensus leaves are recomputed IDENTICALLY per rank.
        for leaf in (0, 1, 2, 9):  # Wbar, W, mu, obj
            assert bits(states[0][leaf]) == bits(states[1][leaf])
        assert hist[0] == hist[1]
        # ...and match the unsharded solver to f32 accumulation accuracy
        # (the split/fused programs differ at the ULP level — the
        # rank_chunked_solver docstring's cross-world caveat).
        np.testing.assert_allclose(
            np.asarray(states[0][0]), np.asarray(m_ref.W),
            rtol=0, atol=1e-4,
        )
        np.testing.assert_allclose(
            hist[0], m_ref.history, rtol=1e-3, atol=1e-3
        )


# ---------------------------------------------------------------------------
# the rank solver's programs are the module's: built once a process
# ---------------------------------------------------------------------------


class TestRankProgramsBuiltOnce:
    @pytest.mark.parametrize("merged", [False, True], ids=["world1", "split"])
    def test_a_second_rank_solver_builds_nothing(self, merged):
        """Two solvers on the same shapes: the second one's chunk traces
        and lowers nothing, with no collective (``admm_chunk``) or with
        one between the halves (``admm_local``, ``admm_merge``)."""
        X, y = make_data()
        maps, params = make_maps(), make_params()
        part = RowPartition(nrows=N, batch_rows=BATCH, world_size=1)
        Z_rows, Y_rows, _ = stream_feature_blocks(
            source_of(X, y, part), maps, part, ElasticParams(prefetch=0),
        )
        prep = prepare_rank_admm(
            "squared", "l2", maps, params, part, 0, Z_rows, Y_rows,
            regression=True,
        )
        merge = (lambda tree: tree) if merged else None

        def chunk():
            solver = rank_chunked_solver(prep, maps, params, merge=merge)
            return solver.step_chunk(solver.init_state(), 3)

        first = chunk()
        with builds() as seen:
            second = chunk()
        assert seen == []
        assert int(second["it"]) == 3
        assert bits(first["inner"][0]) == bits(second["inner"][0])


# ---------------------------------------------------------------------------
# kill/resume through the trainer (in-process; real SIGKILL = slow tier)
# ---------------------------------------------------------------------------


class TestKillResume:
    def _train(self, X, y, maps, params, root, *, resume=False,
               fault_plan=None, train_fault_plan=None):
        part = RowPartition(nrows=N, batch_rows=BATCH, world_size=1)
        trainer = DistributedBlockADMMTrainer(
            "squared", "l2", maps, params,
            ElasticParams(
                checkpoint_dir=str(root), checkpoint_every=2,
                resume=resume, prefetch=0,
            ),
        )
        return trainer.train(
            source_of(X, y, part), part, regression=True,
            fault_plan=fault_plan, train_fault_plan=train_fault_plan,
        )

    def test_train_chunk_kill_resume_bitwise(self, tmp_path):
        X, y = make_data()
        maps, params = make_maps(), make_params()
        m_ref, _ = self._train(X, y, maps, params, tmp_path / "ref")
        with pytest.raises(SimulatedPreemption):
            self._train(
                X, y, maps, params, tmp_path / "ck",
                train_fault_plan=FaultPlan(preempt_after_chunk=1),
            )
        m_res, info = self._train(
            X, y, maps, params, tmp_path / "ck", resume=True
        )
        assert bits(m_ref.W) == bits(m_res.W)
        np.testing.assert_array_equal(m_ref.history, m_res.history)
        assert info["iters"] == params.maxiter

    def test_stream_kill_resume_bitwise(self, tmp_path):
        X, y = make_data()
        maps, params = make_maps(), make_params()
        m_ref, _ = self._train(X, y, maps, params, tmp_path / "ref")
        with pytest.raises(SimulatedPreemption):
            self._train(
                X, y, maps, params, tmp_path / "ck",
                fault_plan=FaultPlan(preempt_after_chunk=0),
            )
        m_res, _ = self._train(
            X, y, maps, params, tmp_path / "ck", resume=True
        )
        assert bits(m_ref.W) == bits(m_res.W)
        np.testing.assert_array_equal(m_ref.history, m_res.history)


# ---------------------------------------------------------------------------
# world/partition mismatch: the typed 109 guard
# ---------------------------------------------------------------------------


class TestWorldMismatch:
    def test_resume_under_changed_partition_raises_109(self, tmp_path):
        X, y = make_data()
        maps, params = make_maps(), make_params()

        def train(batch_rows, *, resume):
            part = RowPartition(
                nrows=N, batch_rows=batch_rows, world_size=1
            )
            trainer = DistributedBlockADMMTrainer(
                "squared", "l2", maps, params,
                ElasticParams(
                    checkpoint_dir=str(tmp_path / "ck"),
                    checkpoint_every=2, resume=resume, prefetch=0,
                ),
            )
            return trainer.train(
                source_of(X, y, part), part, regression=True
            )

        train(BATCH, resume=False)
        with pytest.raises(WorldMismatchError) as ei:
            train(2 * BATCH, resume=True)
        assert ei.value.code == 109


# ---------------------------------------------------------------------------
# guard recovery through a training chunk
# ---------------------------------------------------------------------------


class TestGuardRecovery:
    def test_bad_block_replay_preserves_bits(self, monkeypatch):
        monkeypatch.setenv("SKYLARK_GUARD", "1")
        X, y = make_data()
        maps, params = make_maps(), make_params()
        part = RowPartition(nrows=N, batch_rows=BATCH, world_size=1)

        def train(fault_plan=None):
            trainer = DistributedBlockADMMTrainer(
                "squared", "l2", maps, params,
                ElasticParams(prefetch=0, checkpoint_every=4),
            )
            return trainer.train(
                source_of(X, y, part), part, regression=True,
                fault_plan=fault_plan,
            )

        m_clean, _ = train()
        # Inf-scaled block at batch 2 (one-shot): the chunk sentinel
        # trips at the chunk boundary, the fold replays clean, and the
        # model comes out bit-identical.
        m_fault, info = train(FaultPlan(bad_sketch_at=2))
        assert bits(m_clean.W) == bits(m_fault.W)
        assert info["recovery"]["guarded"]
        actions = [a["action"] for a in info["recovery"]["attempts"]]
        assert "replay" in actions
        # the attempt-0 world verdict records the replay count it psummed
        world = [
            a for a in info["recovery"]["attempts"] if a["action"] == "world"
        ]
        assert world and "chunk_replays=1" in world[0]["detail"]

    def test_guard_off_skips_certification(self, monkeypatch):
        monkeypatch.setenv("SKYLARK_GUARD", "0")
        X, y = make_data()
        part = RowPartition(nrows=N, batch_rows=BATCH, world_size=1)
        trainer = DistributedBlockADMMTrainer(
            "squared", "l2", make_maps(), make_params(),
            ElasticParams(prefetch=0),
        )
        _, info = trainer.train(
            source_of(X, y, part), part, regression=True
        )
        assert info["recovery"]["guarded"] is False
        assert info["recovery"]["attempts"] == []


# ---------------------------------------------------------------------------
# serve hand-off: registry round-trip, dtype-faithful
# ---------------------------------------------------------------------------


class TestServeRoundTrip:
    def test_register_save_load_roundtrip(self, tmp_path):
        from libskylark_tpu.serve.registry import Registry

        X, y = make_data()
        part = RowPartition(nrows=N, batch_rows=BATCH, world_size=1)
        reg = Registry()
        trainer = DistributedBlockADMMTrainer(
            "squared", "l2", make_maps(), make_params(),
            ElasticParams(prefetch=0),
        )
        model, info = trainer.train(
            source_of(X, y, part), part, regression=True,
            registry=reg, register_as="admm-reg",
        )
        assert info["registered"] == "admm-reg"
        assert reg.get_model("admm-reg") is model
        pred = np.asarray(model.predict(jnp.asarray(X)))

        # dtype-faithful save/load → a second registry serves identical
        # bits from disk.
        path = str(tmp_path / "model.json")
        model.save(path)
        reg2 = Registry()
        loaded = reg2.load_model("admm-disk", path)
        assert np.asarray(loaded.W).dtype == np.asarray(model.W).dtype
        assert bits(loaded.W) == bits(model.W)
        np.testing.assert_array_equal(
            np.asarray(loaded.predict(jnp.asarray(X))), pred
        )


# ---------------------------------------------------------------------------
# telemetry: the train.* counter group folds into snapshot()
# ---------------------------------------------------------------------------


class TestTrainTelemetry:
    def test_train_counters_fold_into_snapshot(self, monkeypatch, tmp_path):
        monkeypatch.setenv("SKYLARK_TELEMETRY", "1")
        monkeypatch.setenv(
            "SKYLARK_TELEMETRY_DIR", str(tmp_path / "ledger")
        )
        from libskylark_tpu import telemetry

        X, y = make_data()
        part = RowPartition(nrows=N, batch_rows=BATCH, world_size=1)
        trainer = DistributedBlockADMMTrainer(
            "squared", "l2", make_maps(), make_params(),
            ElasticParams(prefetch=0),
        )
        trainer.train(source_of(X, y, part), part, regression=True)
        snap = telemetry.snapshot()
        assert "train" in snap
        assert snap["train"]["runs"] >= 1
        assert snap["train"]["iterations"] >= 8
        assert snap["train"]["consensus"] >= 8
