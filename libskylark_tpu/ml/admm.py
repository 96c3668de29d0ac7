"""Block-splitting consensus ADMM kernel-machine trainer.

≙ ``BlockADMMSolver`` (``ml/BlockADMM.hpp:16-611``): minimizes
``Σ_i loss(o_i, y_i) + λ·reg(W)`` with ``o_i = Σ_j Z_j(x_i)ᵀ W_j`` over
feature-map blocks j, by ADMM with per-(data-partition × feature-block)
local variables and cached ``(Z·Zᵀ + I)`` Cholesky factors.  The update
equations reproduce the reference train loop (``BlockADMM.hpp:374-590``):

  per iter:  mu_ij −= Wbar;  Obar −= nu
             O    = prox_loss(Obar, 1/ρ; Y)
             W    = prox_reg(Wbar − mu, λ/ρ)
             per block j:  rhs  = Wbar_j − mu_ij_j + ZtObar_j
                                  + Z_j·(del_o/(J+1) + nu)ᵀ
                           Wi_j = (Z_jZ_jᵀ + I)⁻¹ rhs      [cached chol]
                           o_j  = Wi_jᵀ Z_j;  mu_ij_j += Wi_j
                           ZtObar_j = Z_j·o_jᵀ;  sum_o += o_j
             del_o = O − sum_o;  Obar = O − del_o/(J+1);  nu += O − Obar
             Wbar = (Σ_partitions Wi + W)/(P+1);  mu += W − Wbar

TPU re-design of the parallel schedule (SURVEY §2.7 P10): the reference
maps data partitions to MPI ranks and feature blocks to OpenMP threads.
Here data partitions are an explicit **vmapped leading axis** (size P) —
the algorithm is identical for a given P regardless of device count — and
the consensus reduction ``Σ_partitions Wi`` is a plain sum that GSPMD
lowers to a psum over ICI when the P axis is sharded across the mesh.
Feature blocks are an unrolled loop of MXU GEMMs (XLA overlaps them; no
OpenMP needed).  The whole iteration is one jitted function — no host
round-trips inside a step (the reference broadcasts Wbar over MPI every
iteration, ``BlockADMM.hpp:375``).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.scipy.linalg import solve_triangular

from ..core.params import Params
from ..resilient.chunked import ChunkedSolver
from ..sketch.base import Dimension
from ..solvers.prox import get_loss, get_regularizer
from ..utils import compile_cache
from ..utils.timer import PhaseTimer
from .coding import dummy_coding
from .model import FeatureMapModel

__all__ = ["ADMMParams", "BlockADMMSolver"]


@dataclass
class _PreparedRun:
    """Everything ``train``/``chunked`` need that is NOT checkpointable
    state: the realized feature blocks, cached Cholesky factors, targets,
    the jittable step function, and the initial state tuple.  All of it is
    deterministically rebuilt from (X, Y, maps, params) on resume — only
    the state tuple rides the checkpoint."""

    Zs: list
    Ls: list
    Yp: Any
    state0: tuple
    step: Callable
    timer: PhaseTimer
    d: int
    classes: Any
    dtype: Any


@dataclass
class ADMMParams(Params):
    rho: float = 1.0
    lam: float = 0.01  # regularization weight (≙ lambda)
    maxiter: int = 20
    data_partitions: int = 1  # P (≙ MPI size)
    scale_maps: bool = False  # ≙ ScaleFeatureMaps (sqrt(sj/d) per block)


class BlockADMMSolver:
    """Trainer over a list of feature maps (≙ the ctor taking per-block
    ``featureMaps``; pass maps built by ``kernel.create_rft`` as the
    reference's ``GetSolver`` does, ``ml/hilbert.hpp:11-219``)."""

    def __init__(
        self,
        loss: str,
        regularizer: str,
        feature_maps: Sequence,
        params: ADMMParams | None = None,
    ):
        self.loss = get_loss(loss)
        self.regularizer = get_regularizer(regularizer)
        self.maps = list(feature_maps)
        if not self.maps:
            raise ValueError("BlockADMMSolver needs at least one feature map")
        self.params = params or ADMMParams()

    def _apply_map(self, S, Xp, d):
        """Vmapped columnwise feature apply: Xp (P, d, ni) → (P, sj, ni)."""
        Z = jax.vmap(lambda Xc: S.apply(Xc, Dimension.COLUMNWISE))(Xp)
        if self.params.scale_maps:
            Z = Z * jnp.asarray(np.sqrt(S.s / d), Z.dtype)
        return Z

    def _prepare(self, X, Y, classes=None, regression: bool = False) -> _PreparedRun:
        """Shared setup for :meth:`train` and :meth:`chunked`: realize the
        feature blocks, cache the Cholesky factors, build the jittable
        per-iteration step and the initial state tuple."""
        p = self.params
        X = X.todense() if hasattr(X, "todense") else jnp.asarray(X)
        n, d = X.shape
        P = int(p.data_partitions)
        if n % P:
            raise ValueError(f"n={n} not divisible by data_partitions={P}")
        ni = n // P

        label_based = getattr(self.loss, "label_based", False)
        if regression:
            T = jnp.asarray(Y)
            T = T[:, None] if T.ndim == 1 else T
            k = T.shape[1]
            Yp = T.reshape(P, ni, k).transpose(0, 2, 1)
        else:
            T, classes = dummy_coding(Y, classes, dtype=X.dtype)
            k = T.shape[1]
            if label_based:
                # Hinge/logistic take class indices (≙ the reference's
                # crammed losses consuming the raw label vector).
                cls = jnp.asarray(
                    np.searchsorted(np.asarray(classes), np.asarray(Y))
                ).astype(X.dtype)
                Yp = cls.reshape(P, ni)
            else:
                Yp = T.reshape(P, ni, k).transpose(0, 2, 1)

        # Partitioned columnwise layout: Xp (P, d, ni).
        Xp = X.reshape(P, ni, d).transpose(0, 2, 1)
        dtype = X.dtype

        J = len(self.maps)
        sizes = [S.s for S in self.maps]
        starts = np.cumsum([0] + sizes)
        D = int(starts[-1])

        # Phase timers ≙ the reference's ADMM SKYLARK_TIMER instrumentation
        # (transform/iteration/prediction, BlockADMM.hpp:357-365).
        timer = PhaseTimer()
        with timer.phase("transform") as ph:
            Zs = [self._apply_map(S, Xp, d) for S in self.maps]  # (P, sj, ni)
            ph.result = Zs
        # Cached Cholesky of Z·Zᵀ + I per (partition, block)
        # (≙ Cache[j] = inv(Z·Zᵀ + I), BlockADMM.hpp:437-441).
        with timer.phase("factor") as ph:
            Ls = [
                jnp.linalg.cholesky(
                    # highest: default f32 matmul (bf16 passes on TPU) can
                    # push Z·Zᵀ + I indefinite → silent NaN factors.
                    jnp.einsum("pst,put->psu", Z, Z, precision="highest")
                    + jnp.eye(Z.shape[1], dtype=dtype)
                )
                for Z in Zs
            ]
            ph.result = Ls

        rho = jnp.asarray(p.rho, dtype)
        lam = jnp.asarray(p.lam, dtype)
        loss, reg = self.loss, self.regularizer

        def chol_solve(L, B):  # (P, s, s) x (P, s, k)
            Ysol = jax.vmap(lambda l, b: solve_triangular(l, b, lower=True))(L, B)
            return jax.vmap(
                lambda l, b: solve_triangular(l.T, b, lower=False)
            )(L, Ysol)

        # Zs/Ls/Yp enter as ARGUMENTS, not closure captures: jit would
        # embed closed-over device arrays as constants in the serialized
        # program (gigabytes of HLO — rejected/slow on AOT compile
        # services) instead of referencing device-resident buffers.
        def step(state, Zs, Ls, Yp):
            Wbar, W, mu, O, Obar, nu, del_o, mu_ij, ZtObar, _ = state
            mu_ij = mu_ij - Wbar[None]
            Obar = Obar - nu
            O = jax.vmap(lambda ob, y: loss.prox(ob, 1.0 / rho, y))(Obar, Yp)
            W = reg.prox(Wbar - mu, lam / rho)

            sum_o = jnp.zeros_like(O)
            wbar_out = jnp.zeros_like(O)
            Wi = jnp.zeros((P, D, k), dtype)
            mu_ij_new = mu_ij
            ZtObar_new = ZtObar
            dsum = del_o / (J + 1.0) + nu  # (P, k, ni)
            for j in range(J):
                lo, hi = int(starts[j]), int(starts[j + 1])
                Z = Zs[j]  # (P, sj, ni)
                wbar_out = wbar_out + jnp.einsum(
                    "psn,sk->pkn", Z, Wbar[lo:hi]
                )
                rhs = (
                    Wbar[None, lo:hi]
                    - mu_ij[:, lo:hi]
                    + ZtObar[:, lo:hi]
                    + jnp.einsum("psn,pkn->psk", Z, dsum)
                )
                Wij = chol_solve(Ls[j], rhs)  # (P, sj, k)
                o = jnp.einsum("psk,psn->pkn", Wij, Z)
                Wi = Wi.at[:, lo:hi].set(Wij)
                mu_ij_new = mu_ij_new.at[:, lo:hi].add(Wij)
                ZtObar_new = ZtObar_new.at[:, lo:hi].set(
                    jnp.einsum("psn,pkn->psk", Z, o)
                )
                sum_o = sum_o + o

            del_o = O - sum_o
            Obar = O - del_o / (J + 1.0)
            nu = nu + O - Obar
            # Consensus: sum over partitions (psum over ICI when sharded)
            # ≙ the MPI reduce of Wi (BlockADMM.hpp:574-578).
            Wbar = (jnp.sum(Wi, axis=0) + W) / (P + 1.0)
            mu = mu + W - Wbar
            obj = jax.vmap(loss.evaluate)(wbar_out, Yp).sum() + lam * reg.evaluate(Wbar)
            return (Wbar, W, mu, O, Obar, nu, del_o, mu_ij_new, ZtObar_new, obj)

        state = (
            jnp.zeros((D, k), dtype),        # Wbar
            jnp.zeros((D, k), dtype),        # W
            jnp.zeros((D, k), dtype),        # mu
            jnp.zeros((P, k, ni), dtype),    # O
            jnp.zeros((P, k, ni), dtype),    # Obar
            jnp.zeros((P, k, ni), dtype),    # nu
            jnp.zeros((P, k, ni), dtype),    # del_o
            jnp.zeros((P, D, k), dtype),     # mu_ij
            jnp.zeros((P, D, k), dtype),     # ZtObar_ij
            jnp.zeros((), dtype),            # obj
        )
        return _PreparedRun(
            Zs=Zs, Ls=Ls, Yp=Yp, state0=state, step=step, timer=timer,
            d=d, classes=classes, dtype=dtype,
        )

    def train(self, X, Y, classes=None, regression: bool = False,
              Xv=None, Yv=None):
        """X (n, d); Y (n,) labels (classification) or (n,)/(n, t) targets
        (regression).  Optional validation set (Xv, Yv) is scored every
        iteration (≙ the per-iteration validation predict,
        ``BlockADMM.hpp:509-540``) into ``model.val_history``.  Returns a
        ``FeatureMapModel`` (with ``.classes`` and ``.history`` attached).
        BCOO input is densified (the partitioned reshape needs strides)."""
        compile_cache.place()
        p = self.params
        run = self._prepare(X, Y, classes, regression)
        Zs, Ls, Yp = run.Zs, run.Ls, run.Yp
        state, step, timer = run.state0, run.step, run.timer
        d, classes = run.d, run.classes
        have_val = Xv is not None and Yv is not None
        if have_val:
            Xv = Xv.todense() if hasattr(Xv, "todense") else jnp.asarray(Xv)
            Yv = np.asarray(Yv)

        history, val_history = [], []
        if not have_val:
            # All iterations in ONE jitted lax.scan: the per-iteration
            # objective readback costs a full host round-trip and a
            # device sync, so sync once at the end and report the
            # whole objective trace from the returned array.
            @jax.jit
            def run_all(state, Zs, Ls, Yp):
                def body(st, _):
                    st = step(st, Zs, Ls, Yp)
                    return st, st[-1]

                return jax.lax.scan(body, state, None, length=p.maxiter)

            with timer.phase("iteration"):
                state, objs = run_all(state, Zs, Ls, Yp)
                history = [float(o) for o in np.asarray(objs)]
            for it, obj in enumerate(history, 1):
                p.log(1, f"iteration {it} objective {obj:.6e}")
        else:
            step = jax.jit(step)
            for it in range(1, p.maxiter + 1):
                with timer.phase("iteration"):
                    state = step(state, Zs, Ls, Yp)
                    obj = float(state[-1])  # readback syncs the step
                history.append(obj)
                msg = f"iteration {it} objective {obj:.6e}"
                with timer.phase("prediction") as ph:
                    interim = FeatureMapModel(
                        self.maps, state[0], scale_maps=p.scale_maps,
                        input_dim=d,
                    )
                    if regression:
                        pv = np.asarray(interim.predict(Xv))
                        Yv2 = Yv if Yv.ndim > 1 else Yv[:, None]
                        metric = float(
                            np.linalg.norm(pv - Yv2)
                            / max(np.linalg.norm(Yv2), 1e-30)
                        )
                        msg += f" val relerr {metric:.4f}"
                    else:
                        pv = np.asarray(interim.predict_labels(Xv, classes))
                        metric = float((pv == Yv).mean()) * 100
                        msg += f" val accuracy {metric:.2f}"
                val_history.append(metric)
                p.log(1, msg)

        p.log(2, timer.report())
        Wbar = state[0]
        model = FeatureMapModel(
            self.maps, Wbar, scale_maps=p.scale_maps, input_dim=d,
            classes=classes,
        )
        model.history = history
        model.val_history = val_history
        model.timers = timer
        return model

    def chunked(self, X, Y, classes=None, regression: bool = False) -> ChunkedSolver:
        """Preemption-safe ADMM: a ``ChunkedSolver`` whose state pytree is
        (iteration counter, the 10-tuple ADMM state, objective trace) —
        exactly what a resumed process cannot recompute.  The feature
        blocks, Cholesky factors, and targets are rebuilt by
        :meth:`_prepare` on resume (deterministic: counter-based maps,
        pinned-precision factor products), so a run resumed from a chunk
        boundary is bit-identical to the uninterrupted chunked run.
        That kill/resume bit-identity — and the chunked-vs-``train()``
        model parity it rides on — is PINNED by
        ``tests/test_distributed_train.py::TestChunkedContract`` (the
        distributed trainer's per-rank loop reuses this exact
        ``init_state/step_chunk/extract_result`` shape).

        Validation scoring is a ``train``-only feature; drive this with
        ``resilient.ResilientRunner`` and score the returned model.
        """
        p = self.params
        run = self._prepare(X, Y, classes, regression)
        maxiter = int(p.maxiter)

        def init_state():
            return dict(
                it=jnp.zeros((), jnp.int32),
                inner=run.state0,
                objs=jnp.zeros((maxiter,), run.dtype),
            )

        # Zs/Ls/Yp enter as ARGUMENTS for the same reason as in train():
        # jit would bake closed-over device arrays into the program as
        # constants.
        @partial(jax.jit, static_argnames=("num_iters",))
        def _chunk(st, Zs, Ls, Yp, num_iters: int):
            stop = jnp.minimum(st["it"] + num_iters, maxiter)

            def cond(c):
                return c["it"] < stop

            def body(c):
                inner = run.step(c["inner"], Zs, Ls, Yp)
                return dict(
                    it=c["it"] + 1,
                    inner=inner,
                    objs=c["objs"].at[c["it"]].set(inner[-1]),
                )

            return lax.while_loop(cond, body, st)

        def step_chunk(st, num_iters: int):
            return _chunk(st, run.Zs, run.Ls, run.Yp, num_iters)

        def extract_result(st):
            it = int(st["it"])
            model = FeatureMapModel(
                self.maps, st["inner"][0], scale_maps=p.scale_maps,
                input_dim=run.d, classes=run.classes,
            )
            model.history = [float(o) for o in np.asarray(st["objs"][:it])]
            model.val_history = []
            model.timers = run.timer
            return model

        return ChunkedSolver(
            init_state=init_state,
            step_chunk=step_chunk,
            extract_result=extract_result,
            is_done=lambda st: int(st["it"]) >= maxiter,
            iteration=lambda st: int(st["it"]),
            kind="block_admm",
        )
