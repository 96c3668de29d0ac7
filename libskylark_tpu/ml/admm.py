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
             obj  = loss(Σ_j Wbar_jᵀZ_j, Y) at the Wbar the iteration
                    started from, + λ·reg of the one it ends with

TPU re-design of the parallel schedule (SURVEY §2.7 P10): the reference
maps data partitions to MPI ranks and feature blocks to OpenMP threads.
Here data partitions are an explicit **vmapped leading axis** (size P) —
the algorithm is identical for a given P regardless of device count — and
the consensus reduction ``Σ_partitions Wi`` is a plain sum that GSPMD
lowers to a psum over ICI when the P axis is sharded across the mesh.
The whole iteration is one program — no host round-trips inside a step
(the reference broadcasts Wbar over MPI every iteration,
``BlockADMM.hpp:375``).

**Cached or remade** (≙ upstream's ``CacheTransforms``;
``ADMMParams.cache_transforms``).  Cached, every ``Z_j`` is realized for
all rows before the first iteration and kept for the run (``Zs``,
``(P, s_j, n_i)``): the blocks are an unrolled loop of GEMMs over
resident operands.  Remade (upstream's default, and what lets features ×
rows exceed memory), the iteration's operands are X and the maps; block
j's ``Z_j`` is made inside the iteration, held for two products and
dropped: one block is live at a time, the block loop made sequential in
the program by an explicit dependence.  ``None`` caches when all blocks
fit beside X in ``CACHE_FRACTION`` of the device's memory.  The two
routes share one step body.

**A block's products.**  The recurrence above meets ``Z_j`` four times:
``Wbar_jᵀ Z_j`` (the objective's), ``Z_j·dsumᵀ`` (the right-hand
side's), then after the solve ``o_j = Wi_jᵀ Z_j`` and ``ZtObar_j =
Z_j·o_jᵀ``.  A block's reads set the pace (a remade block is a
temporary that is written, read and dropped), so the step reads it
twice on both routes: ``Z_j·dsumᵀ`` before the solve; after it ONE
product whose small operand is ``Wbar_j`` and ``Wi_j`` side by side
along k (both are known by then, ``Wbar_j`` since the iteration began),
the first k columns of the result the objective's, the rest ``o_j``
(summed over the blocks side by side and split once, after the loop);
and ``ZtObar_j = Z_j Z_jᵀ Wi_j = G_j Wi_j`` from the block's Gram
matrix, which ``admm_factor`` forms once a call for ``L_j`` and keeps
beside it (``Gs``: s_j × s_j, no read of the block).

**Split at the consensus sum.**  ``_step`` is ``_local_step`` (up to
``Σ_partitions Wi`` and the loss's partial sum) then ``_merge_step`` in
one trace.  ``ml/distributed.py`` puts its collective between the two
(``admm_local``, ``admm_merge``), and runs ``admm_chunk`` where its
world is one process: every trainer runs this one step.

**Programs.**  Transform, factor and the iteration scan are module-level
``jax.jit`` programs keyed by shapes and a hashable ``_Spec`` (loss,
regularizer, the serialized maps, P, ρ, λ — the last two constants of
the programs, as they always were): a second ``train`` at the same
shapes traces, lowers and compiles nothing.  The maps' draws are
constants of the programs (realized from the counter stream inside).

**Narrow rows.**  When X is narrower than f32 (bfloat16) the features
take X's dtype, as ``RFT.apply`` gives them, and everything else — state,
right-hand sides, ``Z_jZ_jᵀ + I``, factors, solves, the objective — is
f32.  A block then meets an f32 operand of a thin product (k columns) as
three pieces of the operand in the block's dtype, stacked along k: one
MXU pass with 3k columns, f32 accumulation, the operand carried to 24
bits.  f32 and f64 rows keep their dtype throughout.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.scipy.linalg import solve_triangular

from .. import telemetry
from ..core.params import Params
from ..resilient.chunked import ChunkedSolver
from ..sketch.base import Dimension
from ..sketch.rft import _is_narrow
from ..solvers.prox import get_loss, get_regularizer
from ..utils import compile_cache, profiling
from ..utils.timer import PhaseTimer
from .coding import class_indices, dummy_coding
from .model import FeatureMapModel, _Maps

__all__ = ["ADMMParams", "BlockADMMSolver", "CACHE_FRACTION"]

# ``cache_transforms=None`` keeps every feature block for the run when X
# and the blocks together take no more than this share of the memory of
# the device that holds X (the rest is the state, the factors and the
# programs' temporaries); otherwise the blocks are remade.
CACHE_FRACTION = 0.5


@dataclass
class ADMMParams(Params):
    rho: float = 1.0
    lam: float = 0.01  # regularization weight (≙ lambda)
    maxiter: int = 20
    data_partitions: int = 1  # P (≙ MPI size)
    scale_maps: bool = False  # ≙ ScaleFeatureMaps (sqrt(sj/d) per block)
    # ≙ CacheTransforms: keep every Z_j for the run (True), remake each
    # inside every iteration (False), or decide by bytes (None).
    cache_transforms: bool | None = None


@dataclass(frozen=True)
class _Spec:
    """What the programs are keyed by besides their operands' shapes."""

    loss: str
    reg: str
    maps: _Maps
    P: int
    scale_maps: bool
    cached: bool  # the blocks are operands (True) or remade from X (False)
    rho: float
    lam: float

    @property
    def sizes(self):
        return tuple(S.s for S in self.maps.maps)


@dataclass
class _PreparedRun:
    """Everything ``train``/``chunked`` (and a rank of
    ``ml/distributed.py``) need that is NOT checkpointable state: the
    programs' key, their operands (the realized feature blocks or,
    remade, the partitioned X; the cached Cholesky factors and Gram
    matrices; the targets; ρ and λ) and the initial state tuple.  All of
    it is deterministically rebuilt from (X, Y, maps, params) or a rank's
    streamed blocks on resume — only the state tuple rides the
    checkpoint."""

    spec: _Spec
    feats: Any  # Zs [(P, s_j, n_i)] cached, X (n, d) remade
    Ls: list
    Gs: list  # Z_j·Z_jᵀ (P, s_j, s_j)
    Yp: Any
    state0: tuple
    timer: PhaseTimer
    d: int
    classes: Any
    dtype: Any

    @property
    def Zs(self):
        return self.feats if self.spec.cached else None

    @property
    def operands(self):
        return self.feats, self.Ls, self.Gs, self.Yp


def _device_memory_bytes(X):
    """The memory of one device that holds X, or None where the backend
    states none (the CPU)."""
    dev = next(iter(X.devices()))
    return (dev.memory_stats() or {}).get("bytes_limit")


def _cache_fits(X, sizes) -> bool:
    """Do X and every feature block of all its rows fit, a device, in
    ``CACHE_FRACTION`` of the memory?  True where no limit is stated."""
    limit = _device_memory_bytes(X)
    if limit is None:
        return True
    n, d = X.shape
    held = (n * d + n * sum(sizes)) * X.dtype.itemsize / len(X.devices())
    return held <= CACHE_FRACTION * limit


# -- the programs -----------------------------------------------------------


def _block(spec: _Spec, j: int, X):
    """Z_j (P, s_j, n_i).  Cached, ``X`` is the partitioned columnwise
    Xp (P, d, n_i) and this is the apply as it always was.  Remade, it
    is X (n, d) as the caller holds it -- no partitioned or transposed
    copy beside it -- and the block is the rowwise features of its
    partitions seen columnwise, a layout the compiler is free to choose
    (on a v5e it makes (s_j, n_i) straight from the rows at the
    columnwise apply's speed: PERF.md section 6, PR 33)."""
    S = spec.maps.maps[j]
    with jax.named_scope("admm.features"):
        if spec.cached:
            Z = jax.vmap(lambda Xc: S.apply(Xc, Dimension.COLUMNWISE))(X)
        else:
            Xp = X.reshape(spec.P, X.shape[0] // spec.P, X.shape[1])
            Z = jax.vmap(lambda Xr: S.apply(Xr, Dimension.ROWWISE))(Xp)
            Z = Z.transpose(0, 2, 1)
        if spec.scale_maps:
            # the barrier keeps the two scalings two roundings, as they
            # are op by op (the compiler would fold them into one)
            Z = lax.optimization_barrier(Z) * jnp.asarray(np.sqrt(S.s / S.n), Z.dtype)
    return Z


def _pieces(A, dtype):
    """f32 ``A`` as three pieces in the narrower ``dtype`` whose sum is A
    to 24 bits, stacked on a new leading axis.  ``reduce_precision`` and
    not a cast there and back: the TPU compiler folds
    ``convert(convert(x, bf16), f32)`` to x, and the lower pieces with it
    (PERF.md section 6, PR 33)."""
    fi = jnp.finfo(dtype)
    out, r = [], A
    for _ in range(2):
        hi = lax.reduce_precision(r, fi.nexp, fi.nmant)
        out.append(hi.astype(dtype))
        r = r - hi
    out.append(r.astype(dtype))
    return jnp.stack(out)


def _thin(eq, a, b):
    """One of a block's products with the k (or 2k) columns of the state.
    Operands of one dtype: the einsum as it always was.  A block narrower
    than the state: the state's operand goes in as :func:`_pieces` of it
    along a new axis beside k, one product in the block's dtype with f32
    accumulation, and the pieces' results are added."""
    if a.dtype == b.dtype:
        return jnp.einsum(eq, a, b)
    ins, out = eq.split("->")
    ea, eb = ins.split(",")
    if _is_narrow(a.dtype):
        b, eb = _pieces(b, a.dtype), "x" + eb
    else:
        a, ea = _pieces(a, b.dtype), "x" + ea
    return jnp.einsum(
        f"{ea},{eb}->x{out}", a, b, preferred_element_type=jnp.float32
    ).sum(0)


def _gram(Z, dtype):
    """Z·Zᵀ a partition, in the state's dtype."""
    if Z.dtype == dtype:
        # highest: default f32 matmul (bf16 passes on TPU) can push
        # Z·Zᵀ + I indefinite → silent NaN factors.
        return jnp.einsum("pst,put->psu", Z, Z, precision="highest")
    return jnp.einsum("pst,put->psu", Z, Z, preferred_element_type=dtype)


def _chol_solve(L, B):  # (P, s, s) x (P, s, k)
    Ysol = jax.vmap(lambda l, b: solve_triangular(l, b, lower=True))(L, B)
    return jax.vmap(
        lambda l, b: solve_triangular(l.T, b, lower=False)
    )(L, Ysol)


def _after(x, *done):
    """``(x, *done)``, x once ``done`` are computed: the explicit
    dependence that makes the block loop sequential when the blocks are
    remade.  The caller goes on with EVERY output: what nothing reads of
    a barrier is pruned from it, and the dependence with it (the program
    compiled for a v5e then made two blocks side by side: PERF.md
    section 6, PR 34)."""
    return lax.optimization_barrier((x, *done))


@partial(jax.jit, static_argnames=("D", "k", "P", "ni", "dtype"))
def _zero_state(*, D, k, P, ni, dtype):
    """The state every run starts from, in one launch."""
    small, tall = jnp.zeros((D, k), dtype), jnp.zeros((P, k, ni), dtype)
    per = jnp.zeros((P, D, k), dtype)
    # Wbar, W, mu, O, Obar, nu, del_o, mu_ij, ZtObar_ij, obj
    return (small, small, small, tall, tall, tall, tall, per, per,
            jnp.zeros((), dtype))


@partial(jax.jit, static_argnames=("spec",))
def admm_transform(Xp, *, spec: _Spec):
    """Every feature block of the cached route, kept for the run."""
    return [_block(spec, j, Xp) for j in range(len(spec.sizes))]


@partial(jax.jit, static_argnames=("spec", "dtype"))
def admm_factor(feats, *, spec: _Spec, dtype):
    """``(Ls, Gs)``: the Cholesky factor of Z·Zᵀ + I per (partition,
    block) (≙ Cache[j] = inv(Z·Zᵀ + I), BlockADMM.hpp:437-441), and the
    Gram matrices Z·Zᵀ beside them: the iteration takes ``ZtObar_j``
    from them.  Remade, each block is made here for its Gram product and
    dropped."""
    Ls, Gs = [], []
    for j, s in enumerate(spec.sizes):
        if spec.cached:
            Z = feats[j]
        else:
            feats, *Ls = _after(feats, *Ls)
            Z = _block(spec, j, feats)
        with jax.named_scope("admm.factor"):
            G = _gram(Z, dtype)
            Ls.append(jnp.linalg.cholesky(G + jnp.eye(s, dtype=dtype)))
            Gs.append(G)
    return Ls, Gs


def _local_step(spec: _Spec, state, feats, Ls, Gs, Yp):
    """An iteration up to its consensus sum: ``(core, Σ_partitions Wi,
    loss partial)``, ``core`` the state but ``Wbar`` and the objective.
    The operands hold a rank's partitions; ``spec.P`` counts them all.
    They are ARGUMENTS of the programs, never closure captures: jit would
    embed closed-over device arrays as constants in the program."""
    loss, reg = get_loss(spec.loss), get_regularizer(spec.reg)
    J = len(spec.sizes)
    starts = np.cumsum((0,) + spec.sizes)
    D = int(starts[-1])
    Wbar, W, mu, O, Obar, nu, del_o, mu_ij, ZtObar, _ = state
    P, k, dtype = mu_ij.shape[0], Wbar.shape[1], Wbar.dtype
    rho, lam = jnp.asarray(spec.rho, dtype), jnp.asarray(spec.lam, dtype)
    if not spec.cached:
        # nothing of a block is loop-invariant: block 0 waits for the carry
        feats, Wbar = _after(feats, Wbar)

    with jax.named_scope("admm.prox"):
        mu_ij = mu_ij - Wbar[None]
        Obar = Obar - nu
        O = jax.vmap(lambda ob, y: loss.prox(ob, 1.0 / rho, y))(Obar, Yp)
        W = reg.prox(Wbar - mu, lam / rho)

    # Σ_j of (Wbar_jᵀZ_j, o_j) side by side along k, as the stacked
    # product makes them, split once after the loop (two sums split a
    # block at a time cost 1.1 % of a call: PERF.md section 6, PR 34)
    outs = jnp.zeros((P, 2 * k) + O.shape[2:], dtype)
    Wi = jnp.zeros((P, D, k), dtype)
    mu_ij_new = mu_ij
    ZtObar_new = ZtObar
    dsum = del_o / (J + 1.0) + nu  # (P, k, ni)
    for j in range(J):
        lo, hi = int(starts[j]), int(starts[j + 1])
        Z = feats[j] if spec.cached else _block(spec, j, feats)  # (P, sj, ni)
        with jax.named_scope("admm.thin_products"):
            rhs = (
                Wbar[None, lo:hi]
                - mu_ij[:, lo:hi]
                + ZtObar[:, lo:hi]
                + _thin("psn,pkn->psk", Z, dsum)
            )
        with jax.named_scope("admm.block_solve"):
            Wij = _chol_solve(Ls[j], rhs)  # (P, sj, k)
        with jax.named_scope("admm.thin_products"):
            # the block's second and last read: the objective's product
            # rides with o_j's, and Z_j·o_jᵀ = G_j·Wi_j
            both = jnp.concatenate(
                [jnp.broadcast_to(Wbar[lo:hi], Wij.shape), Wij], axis=2)
            outs = outs + _thin("psk,psn->pkn", both, Z)
            zto = jnp.einsum("psu,puk->psk", Gs[j], Wij, precision="highest")
            Wi = Wi.at[:, lo:hi].set(Wij)
            mu_ij_new = mu_ij_new.at[:, lo:hi].add(Wij)
            ZtObar_new = ZtObar_new.at[:, lo:hi].set(zto)
        if not spec.cached:
            # block j + 1 is made when block j's products are done
            feats, outs = _after(feats, outs)

    with jax.named_scope("admm.tail"):
        wbar_out, sum_o = outs[:, :k], outs[:, k:]
        del_o = O - sum_o
        Obar = O - del_o / (J + 1.0)
        nu = nu + O - Obar
        wi = jnp.sum(Wi, axis=0)
        obj = jax.vmap(loss.evaluate)(wbar_out, Yp).sum()
    return (W, mu, O, Obar, nu, del_o, mu_ij_new, ZtObar_new), wi, obj


def _merge_step(spec: _Spec, core, wi, obj):
    """The iteration's end from the consensus sum ``wi`` over every
    partition and the loss's sum ``obj`` over every row."""
    reg = get_regularizer(spec.reg)
    W, mu, O, Obar, nu, del_o, mu_ij, ZtObar = core
    lam = jnp.asarray(spec.lam, W.dtype)
    with jax.named_scope("admm.tail"):
        # Consensus: sum over partitions (psum over ICI when sharded)
        # ≙ the MPI reduce of Wi (BlockADMM.hpp:574-578).
        Wbar = (wi + W) / (spec.P + 1.0)
        mu = mu + W - Wbar
        obj = obj + lam * reg.evaluate(Wbar)
    return (Wbar, W, mu, O, Obar, nu, del_o, mu_ij, ZtObar, obj)


def _step(spec: _Spec, state, feats, Ls, Gs, Yp):
    """One ADMM iteration: both halves in one trace."""
    return _merge_step(spec, *_local_step(spec, state, feats, Ls, Gs, Yp))


admm_step = jax.jit(_step, static_argnames=("spec",))
admm_local = jax.jit(_local_step, static_argnames=("spec",))
admm_merge = jax.jit(_merge_step, static_argnames=("spec",))


@partial(jax.jit, static_argnames=("spec", "maxiter"))
def admm_iterate(state, feats, Ls, Gs, Yp, *, spec: _Spec, maxiter: int):
    """All iterations in ONE ``lax.scan``: the per-iteration objective
    readback costs a full host round-trip and a device sync, so sync
    once at the end and report the whole objective trace from the
    returned array."""

    def body(st, _):
        st = _step(spec, st, feats, Ls, Gs, Yp)
        return st, st[-1]

    return lax.scan(body, state, None, length=maxiter)


@partial(jax.jit, static_argnames=("spec", "maxiter", "num_iters"))
def admm_chunk(st, feats, Ls, Gs, Yp, *, spec: _Spec, maxiter: int, num_iters: int):
    """At most ``num_iters`` iterations of a chunked run's state
    ``dict(it, inner, objs)``: the same step body under a while loop."""
    stop = jnp.minimum(st["it"] + num_iters, maxiter)

    def cond(c):
        return c["it"] < stop

    def body(c):
        inner = _step(spec, c["inner"], feats, Ls, Gs, Yp)
        return dict(
            it=c["it"] + 1,
            inner=inner,
            objs=c["objs"].at[c["it"]].set(inner[-1]),
        )

    return lax.while_loop(cond, body, st)


# -- the solver -------------------------------------------------------------


def _code_targets(loss, Y, classes, regression: bool, P: int, dtype):
    """``(Yp, classes, k)``: the targets of n rows in the programs'
    layout over P partitions of them -- (P, k, n_i), or class indices
    (P, n_i) for a loss that takes labels -- and k, the state's
    columns."""
    label_based = getattr(loss, "label_based", False)
    if regression:
        T = jnp.asarray(Y)
        T = T[:, None] if T.ndim == 1 else T
        k = T.shape[1]
        return T.reshape(P, -1, k).transpose(0, 2, 1), classes, k
    if isinstance(Y, jax.Array):
        # labels on the device are coded there: no read of n
        # labels to the host and no copy back
        cls, classes = class_indices(Y, classes)
        k = len(classes)
        if label_based:
            return cls.astype(dtype).reshape(P, -1), classes, k
        T = jnp.where(cls[:, None] == jnp.arange(k), 1.0, -1.0).astype(dtype)
    else:
        T, classes = dummy_coding(Y, classes, dtype=dtype)
        k = T.shape[1]
        if label_based:
            # Hinge/logistic take class indices (≙ the reference's
            # crammed losses consuming the raw label vector).
            cls = jnp.asarray(
                np.searchsorted(np.asarray(classes), np.asarray(Y))
            ).astype(dtype)
            return cls.reshape(P, -1), classes, k
    return T.reshape(P, -1, k).transpose(0, 2, 1), classes, k


class BlockADMMSolver:
    """Trainer over a list of feature maps (≙ the ctor taking per-block
    ``featureMaps``; pass maps built by ``kernel.create_rft`` as the
    reference's ``GetSolver`` does, ``ml/hilbert.hpp:11-219``)."""

    def __init__(
        self,
        loss: str,
        regularizer: str,
        feature_maps,
        params: ADMMParams | None = None,
    ):
        self.loss = get_loss(loss)
        self.regularizer = get_regularizer(regularizer)
        self.maps = list(feature_maps)
        if not self.maps:
            raise ValueError("BlockADMMSolver needs at least one feature map")
        self.params = params or ADMMParams()

    def _prepare(self, X, Y, classes=None, regression: bool = False) -> _PreparedRun:
        """Shared setup for :meth:`train` and :meth:`chunked`: code the
        labels, pick the route, realize the feature blocks (cached) or
        hand X on (remade), cache the Cholesky factors, build the
        initial state tuple."""
        p = self.params
        X = X.todense() if hasattr(X, "todense") else jnp.asarray(X)
        n, d = X.shape
        P = int(p.data_partitions)
        if n % P:
            raise ValueError(f"n={n} not divisible by data_partitions={P}")
        ni = n // P
        # Narrow rows: the features take X's dtype, everything else is f32.
        dtype = jnp.dtype(jnp.float32) if _is_narrow(X.dtype) else X.dtype

        # Phase timers ≙ the reference's ADMM SKYLARK_TIMER instrumentation
        # (transform/iteration/prediction, BlockADMM.hpp:357-365); each
        # phase opens the stage span of its name.
        timer = PhaseTimer()
        with timer.phase("admm.labels") as ph:
            Yp, classes, k = ph.result = _code_targets(
                self.loss, Y, classes, regression, P, dtype)

        sizes = [S.s for S in self.maps]
        D = int(sum(sizes))
        cached = p.cache_transforms
        if cached is None:
            cached = _cache_fits(X, sizes)
        spec = _Spec(
            loss=self.loss.name, reg=self.regularizer.name,
            maps=_Maps(self.maps), P=P, scale_maps=bool(p.scale_maps),
            cached=bool(cached), rho=float(p.rho), lam=float(p.lam),
        )
        with timer.phase("admm.transform") as ph:
            if spec.cached:
                # Partitioned columnwise layout: Xp (P, d, ni).
                Xp = X.reshape(P, ni, d).transpose(0, 2, 1)
                feats = profiling.launch(
                    admm_transform, Xp, spec=spec)  # [(P, sj, ni)]
            else:
                feats = X  # the blocks are made from it inside the programs
            ph.result = feats
        with timer.phase("admm.factor") as ph:
            Ls, Gs = ph.result = profiling.launch(
                admm_factor, feats, spec=spec, dtype=dtype)

        state = _zero_state(D=D, k=int(k), P=P, ni=ni, dtype=dtype)
        return _PreparedRun(
            spec=spec, feats=feats, Ls=Ls, Gs=Gs, Yp=Yp, state0=state, timer=timer, d=d, classes=classes,
            dtype=dtype,
        )

    def _model(self, run: _PreparedRun, Wbar, history, val_history=()):
        p = self.params
        model = FeatureMapModel(
            self.maps, Wbar, scale_maps=p.scale_maps, input_dim=run.d,
            classes=run.classes,
        )
        model.history = list(history)
        model.val_history = list(val_history)
        model.timers = run.timer
        model.info = {
            "iterations": len(model.history),
            "feature_blocks": len(self.maps),
            "transforms_cached": int(run.spec.cached),
            # passes over X an iteration: a remade block is held between
            # its products before and after the solve, not made twice
            "feature_passes": 0 if run.spec.cached else 1,
            # reads of a block an iteration (the module docstring)
            "block_reads": 2,
            "objective": model.history[-1] if model.history else None,
        }
        return model

    def train(self, X, Y, classes=None, regression: bool = False,
              Xv=None, Yv=None):
        """X (n, d); Y (n,) labels (classification) or (n,)/(n, t) targets
        (regression).  Optional validation set (Xv, Yv) is scored every
        iteration (≙ the per-iteration validation predict,
        ``BlockADMM.hpp:509-540``) into ``model.val_history``.  Returns a
        ``FeatureMapModel`` (with ``.classes``, ``.history`` and
        ``.info`` attached).  BCOO input is densified (the partitioned
        reshape needs strides)."""
        with telemetry.span("block_admm_train"):
            return self._train(X, Y, classes, regression, Xv, Yv)

    def _train(self, X, Y, classes, regression, Xv, Yv):
        compile_cache.place()
        p = self.params
        run = self._prepare(X, Y, classes, regression)
        state, timer = run.state0, run.timer
        d, classes = run.d, run.classes
        have_val = Xv is not None and Yv is not None
        if have_val:
            Xv = Xv.todense() if hasattr(Xv, "todense") else jnp.asarray(Xv)
            Yv = np.asarray(Yv)

        history, val_history = [], []
        if not have_val:
            with timer.phase("admm.iterate") as ph:
                state, objs = ph.result = profiling.launch(
                    admm_iterate, state, *run.operands, spec=run.spec,
                    maxiter=int(p.maxiter))
            with timer.phase("admm.result"):
                history = [float(o) for o in np.asarray(objs)]
            for it, obj in enumerate(history, 1):
                p.log(1, f"iteration {it} objective {obj:.6e}")
        else:
            for it in range(1, p.maxiter + 1):
                with timer.phase("admm.iterate"):
                    state = profiling.launch(
                        admm_step, run.spec, state, *run.operands)
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
        return self._model(run, state[0], history, val_history)

    def chunked(self, X, Y, classes=None, regression: bool = False) -> ChunkedSolver:
        """Preemption-safe ADMM: a ``ChunkedSolver`` whose state pytree is
        (iteration counter, the 10-tuple ADMM state, objective trace) —
        exactly what a resumed process cannot recompute.  The feature
        blocks (or, remade, X itself), Cholesky factors, and
        targets are rebuilt by :meth:`_prepare` on resume (deterministic:
        counter-based maps, pinned-precision factor products), so a run
        resumed from a chunk boundary is bit-identical to the
        uninterrupted chunked run.
        That kill/resume bit-identity — and the chunked-vs-``train()``
        model parity it rides on — is PINNED by
        ``tests/test_distributed_train.py::TestChunkedContract`` (the
        distributed trainer's per-rank loop reuses this exact
        ``init_state/step_chunk/extract_result`` shape).

        Validation scoring is a ``train``-only feature; drive this with
        ``resilient.ResilientRunner`` and score the returned model.
        """
        run = self._prepare(X, Y, classes, regression)
        maxiter = int(self.params.maxiter)

        def init_state():
            return dict(
                it=jnp.zeros((), jnp.int32),
                inner=run.state0,
                objs=jnp.zeros((maxiter,), run.dtype),
            )

        def step_chunk(st, num_iters: int):
            return profiling.launch(
                admm_chunk, st, *run.operands, spec=run.spec,
                maxiter=maxiter, num_iters=num_iters)

        def extract_result(st):
            it = int(st["it"])
            return self._model(
                run, st["inner"][0],
                [float(o) for o in np.asarray(st["objs"][:it])])

        return ChunkedSolver(
            init_state=init_state,
            step_chunk=step_chunk,
            extract_result=extract_result,
            is_done=lambda st: int(st["it"]) >= maxiter,
            iteration=lambda st: int(st["it"]),
            kind="block_admm",
        )
