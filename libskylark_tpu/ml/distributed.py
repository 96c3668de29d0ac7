"""Elastic multi-host BlockADMM kernel-machine training.

≙ the reference's MPI training topology (``ml/BlockADMM.hpp:374-590``
maps data partitions to ranks and broadcasts ``Wbar`` every iteration)
rebuilt on this library's substrates: each rank **streams** its row
partition of the training set through
:func:`~libskylark_tpu.streaming.elastic.elastic_run_stream` (manifest /
handshake / epoch-fence contract, code 109/110/111 ladder), materializes
its random-feature blocks batch-by-batch through
:func:`~libskylark_tpu.plans.apply_rowwise_bucketed` (plan-compiled
executables, bucket-ladder bounded), and runs
:class:`~libskylark_tpu.ml.admm.BlockADMMSolver`'s cached route over
them under the resilient ``init_state/step_chunk/extract_result``
contract: the factors, the targets, the initial state and the step are
``ml/admm.py``'s own, and consensus merges ONCE per outer iteration
with a single :func:`~libskylark_tpu.parallel.collectives.cross_host_psum`
between the step's two halves (``admm_local``, ``admm_merge``).

Bitwise contracts (pinned by ``tests/test_distributed_train.py``):

- **world=1 parity** — a single-process distributed run reproduces
  ``BlockADMMSolver.train`` bit-for-bit: the rowwise bucketed feature
  materialization equals ``_prepare``'s columnwise vmapped apply after
  the partition reshape, and with no collective to cross the iteration
  runs ``admm_chunk``, the program ``BlockADMMSolver.chunked`` runs
  (the world>1 split compiles the two halves as separate XLA programs
  whose constant-folding rewrites can differ at the ULP level, so the
  split is reserved for real collectives — see
  :func:`rank_chunked_solver`).
- **kill/resume** — commits happen only after a chunk's final consensus
  psum completed on EVERY rank, so all ranks durably hold the same
  chunk boundary; a SIGKILLed-and-resumed run replays from that
  boundary and reproduces the uninterrupted model bit-for-bit (same
  blocks, same order, same IEEE ops).
- **consensus decomposition** — global consensus leaves (``Wbar``,
  ``W``, ``mu``, ``obj``) are recomputed identically on every rank from
  the psum-merged ``Σ_partitions Wi``; per-partition leaves stay
  rank-local and never cross the wire.

The policy layer decides the precision rung (bf16 operand rounding
with f32 accumulation, kind ``"train"``); attempt 0 is
guard-certified and a bad certificate on ANY rank escalates EVERY rank
back to full precision (world verdict via a second psum), recorded in
``info["recovery"]`` and observed back into the profile store.
``resume_policy="repartition"`` rides PR 7's
:func:`~libskylark_tpu.streaming.repartition.resolve_resume`: feature
buffers are row-slot (positional, not sum-decomposable), so a world
change re-streams the NEW share at the bumped epoch — within an epoch
the run stays resumable and bit-for-bit.
"""

from __future__ import annotations

import os
from typing import Callable, Sequence

import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import guard, policy, telemetry
from ..parallel.collectives import cross_host_psum
from ..plans import apply_rowwise_bucketed, bucket_for, donating_jit, pad_rows
from ..resilient.chunked import ChunkedSolver
from ..resilient.runner import ResilientParams, ResilientRunner
from ..solvers.prox import get_loss, get_regularizer
from ..streaming.drivers import _result_dtype
from ..streaming.elastic import (
    ElasticParams,
    RowPartition,
    _make_watchdog,
    _require_real_world,
    _resolve_world,
    elastic_run_stream,
    host_dir,
)
from ..streaming.repartition import resolve_resume
from ..sketch.rft import _is_narrow
from ..utils import compile_cache, profiling
from ..utils.exceptions import InvalidParameters
from ..utils.timer import PhaseTimer
from .admm import (
    ADMMParams,
    _code_targets,
    _PreparedRun,
    _Spec,
    _zero_state,
    admm_chunk,
    admm_factor,
    admm_local,
    admm_merge,
)
from .model import FeatureMapModel, _Maps

__all__ = [
    "KIND",
    "DistributedBlockADMMTrainer",
    "prepare_rank_admm",
    "rank_chunked_solver",
    "stream_feature_blocks",
    "validate_train_partition",
]

KIND = "distributed_block_admm"


def validate_train_partition(partition: RowPartition, data_partitions: int) -> int:
    """Check that every rank's row range covers WHOLE ADMM data
    partitions; returns the rows-per-partition ``ni``.

    The consensus math needs each of the ``P`` data partitions to live
    entirely on one rank (per-partition leaves ``O/Obar/nu/del_o/mu_ij/
    ZtObar`` are rank-local); a partition split across ranks has no
    owner.  Pick ``batch_rows`` and world sizes whose
    :meth:`RowPartition.row_range` boundaries land on multiples of
    ``nrows / data_partitions``.
    """
    P = int(data_partitions)
    n = int(partition.nrows)
    if P < 1:
        raise InvalidParameters(f"data_partitions must be >= 1, got {P}")
    if n % P:
        raise InvalidParameters(
            f"n={n} not divisible by data_partitions={P}"
        )
    ni = n // P
    for r in range(partition.world_size):
        r0, r1 = partition.row_range(r)
        if r1 <= r0:
            raise InvalidParameters(
                f"rank {r} owns no rows ([{r0}, {r1})); every rank needs "
                "at least one data partition"
            )
        if r0 % ni or r1 % ni:
            raise InvalidParameters(
                f"rank {r} rows [{r0}, {r1}) don't align with the "
                f"{P}-partition boundaries (every {ni} rows); choose "
                "batch_rows so partition boundaries land on batch "
                "boundaries"
            )
    return ni


def stream_feature_blocks(
    source,
    maps: Sequence,
    partition: RowPartition,
    params: ElasticParams | None = None,
    *,
    dtype=None,
    targets: int = 1,
    scale_maps: bool = False,
    kind: str = KIND,
    fault_plan=None,
    report=None,
    epoch: int = 0,
):
    """This rank's feature-block materialization pass.

    Streams the rank's row window via :func:`elastic_run_stream` and
    applies every feature map to each batch through
    :func:`apply_rowwise_bucketed` (``pad_out=True``: fixed bucket
    shapes, padded rows zeroed inside the executable), writing the rows
    into row-slot buffers at the batch's local offset.  Padded rows
    temporarily clobber slots the NEXT batch overwrites, so replays and
    resumes refold bit-identically; the buffers over-allocate by one
    bucket so the final batch's padding never clips.

    Returns ``(Z_rows, Y_rows, local_batches)`` — ``Z_rows[j]`` is the
    ``(ni_local, s_j)`` rowwise feature block of map ``j`` (bitwise
    equal to the in-process ``_prepare`` apply after the partition
    reshape), ``Y_rows`` the ``(ni_local, targets)`` target rows.
    """
    params = params or ElasticParams()
    rank, world = _resolve_world(params)
    partition.validate_world(rank, world)
    r0, r1 = partition.row_range(rank)
    ni = r1 - r0
    dt = _result_dtype(dtype)
    t = int(targets)
    d = int(maps[0].n) if maps else None
    # One bucket of margin absorbs the largest padded batch the ladder
    # can produce for this stream's batch size.
    margin = bucket_for(max(1, int(partition.batch_rows)))
    nbuf = ni + margin

    def init_at(row0: int):
        return {
            "rows": np.asarray(row0, np.int64),
            "y": jnp.zeros((nbuf, t), dt),
            "z": [jnp.zeros((nbuf, int(S.s)), dt) for S in maps],
        }

    write = donating_jit(
        lambda buf, blk, off: lax.dynamic_update_slice(
            buf, blk, (off, jnp.asarray(0, jnp.int32))
        ),
        donate_argnums=(0,),
    )

    def step(acc, batch, index):
        X_b, y_b = batch
        if hasattr(X_b, "todense"):
            X_b = X_b.todense()
        k = int(X_b.shape[0])
        off = jnp.asarray(int(acc["rows"]) - r0, jnp.int32)
        zs = []
        for S, buf in zip(maps, acc["z"]):
            Zp, _ = apply_rowwise_bucketed(S, X_b, pad_out=True, true_rows=k)
            if scale_maps:
                Zp = Zp * jnp.asarray(np.sqrt(S.s / d), Zp.dtype)
            zs.append(write(buf, jnp.asarray(Zp, dt), off))
        yb = jnp.asarray(y_b, dt).reshape(k, t)
        yb = jnp.asarray(pad_rows(yb, bucket_for(k)))
        return {
            "rows": np.asarray(int(acc["rows"]) + k, np.int64),
            "y": write(acc["y"], yb, off),
            "z": zs,
        }

    acc, nbatches = elastic_run_stream(
        source, step, init_at(r0), partition, params,
        kind=kind, fault_plan=fault_plan, report=report, epoch=epoch,
    )
    rows = int(acc["rows"])
    if rows != r1:
        raise ValueError(
            f"rank {rank} folded rows [{r0}, {rows}) but its partition "
            f"share is [{r0}, {r1}); the source and partition disagree"
        )
    Z_rows = [buf[:ni] for buf in acc["z"]]
    Y_rows = acc["y"][:ni]
    return Z_rows, Y_rows, int(nbatches)


def prepare_rank_admm(
    loss,
    regularizer,
    maps: Sequence,
    admm: ADMMParams,
    partition: RowPartition,
    rank: int,
    Z_rows: Sequence,
    Y_rows,
    *,
    classes=None,
    regression: bool = False,
    compute_dtype=None,
) -> _PreparedRun:
    """This rank's run of ``BlockADMMSolver``'s cached route: its
    partitions of the streamed blocks, their Cholesky factors and Gram
    matrices (``admm_factor``), its targets and the initial state, with
    the program key ``spec`` whose ``P`` counts the partitions of every
    rank (the consensus denominator).

    ``compute_dtype`` (the policy precision rung) rounds the feature
    blocks through the low dtype before factoring — operand compression
    with full-precision accumulation; ``None`` keeps the historical
    full-precision path bitwise.
    """
    loss = get_loss(loss) if isinstance(loss, str) else loss
    reg = get_regularizer(regularizer) if isinstance(regularizer, str) else regularizer
    P_total = int(admm.data_partitions)
    ni_p = validate_train_partition(partition, P_total)
    r0, r1 = partition.row_range(int(rank))
    P_local = (r1 - r0) // ni_p
    # Narrow rows: the features keep their dtype, everything else is f32.
    dtype = Z_rows[0].dtype
    dtype = jnp.dtype(jnp.float32) if _is_narrow(dtype) else dtype
    spec = _Spec(
        loss=loss.name, reg=reg.name, maps=_Maps(list(maps)), P=P_total,
        scale_maps=bool(admm.scale_maps), cached=True, rho=float(admm.rho),
        lam=float(admm.lam),
    )

    timer = PhaseTimer()
    with timer.phase("transform") as ph:
        # (ni_local, sj) row blocks → the partitioned columnwise layout
        # (P_local, sj, ni) of the in-process trainer (bitwise: rowwise
        # apply is the transpose of the columnwise apply per row).
        Zs = [
            Z.reshape(P_local, ni_p, Z.shape[1]).transpose(0, 2, 1)
            for Z in Z_rows
        ]
        if compute_dtype is not None:
            cd = jnp.dtype(compute_dtype)
            Zs = [Z.astype(cd).astype(Z.dtype) for Z in Zs]
        ph.result = Zs

    if not regression and classes is None and partition.world_size > 1:
        raise InvalidParameters(
            "distributed classification needs the GLOBAL class set "
            "passed explicitly (each rank only sees its own labels)"
        )
    Y = Y_rows if regression else np.asarray(Y_rows)[:, 0]
    Yp, classes, k = _code_targets(loss, Y, classes, regression, P_local, dtype)

    with timer.phase("factor") as ph:
        Ls, Gs = ph.result = admm_factor(Zs, spec=spec, dtype=dtype)

    state0 = _zero_state(D=sum(spec.sizes), k=int(k), P=P_local, ni=ni_p, dtype=dtype)
    return _PreparedRun(
        spec=spec, feats=Zs, Ls=Ls, Gs=Gs, Yp=Yp, state0=state0, timer=timer,
        d=int(maps[0].n), classes=classes, dtype=dtype,
    )


def rank_chunked_solver(
    prep: _PreparedRun,
    maps: Sequence,
    admm: ADMMParams,
    *,
    merge: Callable | None = None,
) -> ChunkedSolver:
    """This rank's training loop as a ``ChunkedSolver``.

    State pytree ``dict(it, inner, objs)`` — the same shape as
    ``BlockADMMSolver.chunked``'s, with per-partition leaves sized to
    this rank's share.

    ``merge=None`` (world=1 / no collective) launches ``admm_chunk``,
    the program ``BlockADMMSolver.chunked`` launches, so the world=1
    trainer is bitwise-identical to the in-process ``train()``.  A
    callable ``merge`` (the distributed trainer passes the watchdogged
    ``cross_host_psum``) runs the split schedule ``admm_local → merge →
    admm_merge`` an iteration: XLA compiles the two halves as separate
    programs, whose value-changing rewrites (e.g. divide-by-constant →
    multiply-by-reciprocal) may differ from the fused program's at the
    ULP level — so cross-WORLD-SIZE bit-identity is not promised, while
    within a world size every rank computes the same bits and
    kill/resume reproduces the uninterrupted run bit-for-bit (same
    programs, same blocks, same order).  All three are module-level
    programs: a second solver at the same shapes builds nothing.
    Checkpoint commits happen only AFTER a chunk's final merge completed
    collectively, so every rank durably holds the same chunk boundary
    on any kill — the lockstep resume is exact.
    """
    maxiter = int(admm.maxiter)
    spec, operands = prep.spec, prep.operands

    def init_state():
        return dict(
            it=jnp.zeros((), jnp.int32),
            inner=prep.state0,
            objs=jnp.zeros((maxiter,), prep.dtype),
        )

    def step_chunk(st, num_iters: int):
        it = int(st["it"])
        stop = min(it + int(num_iters), maxiter)
        if merge is None:
            st = profiling.launch(
                admm_chunk, st, *operands, spec=spec, maxiter=maxiter,
                num_iters=int(num_iters))
        else:
            # A restored checkpoint hands back host numpy leaves; the
            # programs accept them, but the objs trace needs jnp's .at.
            inner, objs = st["inner"], jnp.asarray(st["objs"])
            for i in range(it, stop):
                core, wi, obj = profiling.launch(admm_local, spec, inner, *operands)
                g = merge({"wi": wi, "obj": obj})
                inner = profiling.launch(
                    admm_merge, spec, core, jnp.asarray(g["wi"]),
                    jnp.asarray(g["obj"]))
                objs = objs.at[i].set(inner[-1])
            st = dict(it=jnp.asarray(stop, jnp.int32), inner=inner, objs=objs)
        if stop > it and telemetry.enabled():
            telemetry.inc("train.iterations", stop - it)
            telemetry.inc("train.consensus", stop - it)
        return st

    def extract_result(st):
        it = int(st["it"])
        model = FeatureMapModel(
            list(maps), st["inner"][0], scale_maps=admm.scale_maps,
            input_dim=prep.d, classes=prep.classes,
        )
        model.history = [float(o) for o in np.asarray(st["objs"][:it])]
        model.val_history = []
        model.timers = prep.timer
        model.iterations = it
        # Prox-vs-consensus gap ‖W − Wbar‖_F: identical on every rank
        # (both leaves are global), the CLI's post-train report metric.
        model.consensus_residual = float(
            jnp.linalg.norm(st["inner"][1] - st["inner"][0])
        )
        return model

    return ChunkedSolver(
        init_state=init_state,
        step_chunk=step_chunk,
        extract_result=extract_result,
        is_done=lambda st: int(st["it"]) >= maxiter,
        iteration=lambda st: int(st["it"]),
        kind=KIND,
    )


class DistributedBlockADMMTrainer:
    """Multi-host elastic BlockADMM trainer (≙ the reference's MPI
    ``skylark_ml`` training topology).

    Every process of the ``jax.distributed`` world calls :meth:`train`
    with the same arguments; each streams its own row partition, trains
    in lockstep (one psum per outer iteration), and returns the same
    model bit-for-bit — no broadcast needed.  For simulated-rank tests
    compose :func:`stream_feature_blocks` / :func:`prepare_rank_admm` /
    :func:`rank_chunked_solver` directly and merge by hand.
    """

    def __init__(
        self,
        loss: str,
        regularizer: str,
        feature_maps: Sequence,
        params: ADMMParams | None = None,
        elastic: ElasticParams | None = None,
    ):
        self.loss = get_loss(loss)
        self.regularizer = get_regularizer(regularizer)
        self.maps = list(feature_maps)
        if not self.maps:
            raise InvalidParameters(
                "DistributedBlockADMMTrainer needs at least one feature map"
            )
        self.params = params or ADMMParams()
        self.elastic = elastic or ElasticParams()

    def train(
        self,
        source,
        partition: RowPartition,
        *,
        classes=None,
        regression: bool = False,
        dtype=None,
        targets: int | None = None,
        fault_plan=None,
        train_fault_plan=None,
        compute_dtype=None,
        registry=None,
        register_as: str | None = None,
        epoch: int = 0,
    ):
        """Train over the partitioned stream; returns ``(model, info)``.

        ``source`` is the GLOBAL batch factory (``f(start_batch) →
        iterator`` of ``(X_batch, y_batch)``) every rank receives;
        ``fault_plan`` rides the streaming pass, ``train_fault_plan``
        the iteration runner (they count different chunk clocks).
        ``registry``/``register_as`` land the trained model in a serve
        registry at end of training.
        """
        compile_cache.place()
        p, ep = self.params, self.elastic
        kind = KIND
        ni_p = validate_train_partition(partition, p.data_partitions)
        _require_real_world(partition)
        rank, world = _resolve_world(ep)
        partition.validate_world(rank, world)
        r0, r1 = partition.row_range(rank)
        dt = _result_dtype(dtype)
        t = int(targets or 1)
        D = int(sum(int(S.s) for S in self.maps))
        guarded = guard.enabled()
        report = (
            guard.RecoveryReport(stage=kind)
            if guarded
            else guard.RecoveryReport.disabled(kind)
        )
        if telemetry.enabled():
            telemetry.inc("train.runs")

        # Policy: the "train" kind decides only the precision rung (the
        # route IS the consensus trainer); an empty/immature store keeps
        # the full-precision default bitwise.
        k_policy = len(classes) if classes is not None else t
        decision = policy.consult(
            "train", m=partition.nrows, n=D, targets=k_policy, dtype=dt,
            sketch_size=D, guard_on=guarded,
        )
        cd = compute_dtype if compute_dtype is not None else decision.compute_dtype

        plan = None
        replay = None
        if getattr(ep, "resume_policy", "strict") == "repartition":
            epoch, plan = resolve_resume(
                ep.checkpoint_dir, partition, kind=kind, params=ep
            )
            if plan is not None:
                # Feature buffers are row-slot (positional), not
                # sum-decomposable: a world change re-streams the NEW
                # share fresh at the bumped epoch instead of merging
                # durable refs.  Within that epoch the stream and the
                # ADMM state keep their own checkpoints, so a second
                # interruption resumes the recovery bit-for-bit.
                replay = plan.replay_info()
                if telemetry.enabled():
                    telemetry.inc("train.repartitions")
        watchdog = (
            _make_watchdog(ep, ep.checkpoint_dir, rank, world, epoch)
            if ep.checkpoint_dir
            else None
        )

        with telemetry.span("train.stream", kind=kind, rank=rank):
            Z_rows, Y_rows, nbatches = stream_feature_blocks(
                source, self.maps, partition, ep, dtype=dt, targets=t,
                scale_maps=p.scale_maps, kind=kind, fault_plan=fault_plan,
                report=report, epoch=epoch,
            )

        def _prep(rung):
            with telemetry.span("train.factor", kind=kind, rung=str(rung)):
                return prepare_rank_admm(
                    self.loss, self.regularizer, self.maps, p, partition,
                    rank, Z_rows, Y_rows, classes=classes,
                    regression=regression, compute_dtype=rung,
                )

        prep = _prep(cd)
        escalated = False
        if guarded:
            # Attempt-0 certification of the (possibly precision-rounded)
            # factors — and the verdict is a WORLD decision: psum the
            # ok/not-ok flags plus the chunk-sentinel replay counts so
            # every rank takes the same rung even when only one saw the
            # failure.
            ok = bool(guard.tree_all_finite(prep.Ls)) and bool(
                guard.tree_all_finite(prep.Zs)
            )
            local_replays = sum(
                1 for a in report.attempts if a.action == "replay"
            )
            votes = cross_host_psum(
                np.asarray(
                    [0.0 if ok else 1.0, float(local_replays)], np.float64
                ),
                watchdog=watchdog,
                phase="verdict",
            )
            world_bad, world_replays = int(votes[0]), int(votes[1])
            report.record(
                "initial",
                verdict=guard.OK if not world_bad else guard.FALLBACK,
                detail=f"factor finiteness at rung {cd or str(dt)}",
            )
            report.record(
                "world",
                detail=(
                    f"psum verdict over {world} rank(s): bad_certs="
                    f"{world_bad}, chunk_replays={world_replays}"
                ),
            )
            if world_bad:
                if cd is None:
                    raise guard.NumericalHealthError(
                        "non-finite Cholesky factors at full precision",
                        stage=kind, report=report,
                    )
                # f32 escalation rung: rebuild factors at the streamed
                # dtype, recorded for the profile store.
                report.record(
                    "escalate", verdict=guard.FALLBACK,
                    detail=f"{cd} factors non-finite; full-precision "
                    "rebuild (world verdict)",
                )
                report.recovered = True
                decision.escalated = True
                escalated = True
                cd = None
                prep = _prep(None)
                if telemetry.enabled():
                    telemetry.inc("train.escalations")

        # world=1: no collective → ``admm_chunk`` (bitwise parity with
        # ``BlockADMMSolver.train``).  world>1: the split schedule with
        # the watchdogged psum at the seam.
        chunked = rank_chunked_solver(
            prep, self.maps, p,
            merge=(
                None
                if world == 1
                else lambda tree: cross_host_psum(
                    tree, watchdog=watchdog, phase="consensus"
                )
            ),
        )
        rp = ResilientParams(
            checkpoint_dir=(
                os.path.join(host_dir(ep.checkpoint_dir, rank, epoch), "train")
                if ep.checkpoint_dir
                else None
            ),
            checkpoint_every=ep.checkpoint_every,
            keep_last=ep.keep_last,
            resume=ep.resume,
            expect_epoch=(int(epoch) if ep.checkpoint_dir else None),
        )
        runner = ResilientRunner(
            chunked, rp,
            metadata={
                "elastic": {
                    "rank": rank, "world": world, "epoch": int(epoch),
                    "signature": int(partition.signature()),
                }
            },
            fault_plan=train_fault_plan,
        )
        with telemetry.span("train.iterate", kind=kind):
            model = runner.run()

        rung = str(cd) if cd else str(np.dtype(dt))
        info = {
            "rows": int(partition.nrows),
            "batches": int(partition.num_batches),
            "local_batches": int(nbatches),
            "world_size": int(partition.world_size),
            "rank": int(rank),
            "data_partitions": int(p.data_partitions),
            "features": D,
            "blocks": len(self.maps),
            "iters": int(model.iterations),
            "objective": model.history[-1] if model.history else None,
            "consensus_residual": model.consensus_residual,
            "precision": rung,
            "escalated": escalated,
            "resume_policy": getattr(ep, "resume_policy", "strict"),
            "epoch": int(epoch),
            "recovery": report.to_dict(),
            "replay": replay,
            "policy": decision.to_dict(),
            "registered": register_as,
        }
        model.info = info
        bf16_note = None
        if decision.compute_dtype == "bfloat16":
            bf16_note = "fail" if escalated else "ok"
        policy.observe(
            decision, info, default_size=D, bf16=bf16_note, batches=nbatches
        )
        if registry is not None and register_as:
            # End-of-training serve hand-off: every rank holds identical
            # bits, so registering locally is world-consistent.
            registry.register_model(register_as, model)
            if telemetry.enabled():
                telemetry.inc("train.registered")
        telemetry.run_summary(kind, info)
        return model, info
