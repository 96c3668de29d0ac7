"""Compiled sketch-apply plans: fused jit executables behind a cache.

The eager sketch path dispatches the counter-stream realization plus the
matmul/segment-sum as dozens of op-by-op XLA calls; a *plan* compiles the
whole apply into one fused ``jax.jit`` executable and caches it process-
wide (``cache.PLAN_CACHE``) keyed on the serialized sketch + the abstract
input signature, so repeated applies — every batch of a streaming pass,
every sweep of a sketch-and-solve loop, every sketch object rebuilt from
the same JSON — reuse one executable instead of re-tracing.

Three plan kinds:

- ``apply``: the full ``S.apply(A, dim)`` — literally the same function
  the eager path runs, traced once.  jit does not reorder the math (the
  matmul is one primitive either way; elementwise fusion is per-element
  exact), so the planned result is BITWISE identical to eager — the hard
  contract ``tests/test_plans.py`` pins for JLT/CWT/MMT/RFT in both dims.
- ``slice``: the streaming COLUMNWISE accumulation step
  ``acc + Omega[:, start:start+k] @ block`` with a TRACED ``start``
  (counter windows address traced offsets exactly — the P5 invariant) and
  the block padded up to the bucket ladder, so ONE executable serves all
  ragged batches of a bucket; ``acc`` is donated on backends that honor
  donation, eliminating the accumulator double-buffer.
- ``rowwise``: the streaming ROWWISE per-batch sketch on a bucketed
  block, with the transform's counter-realized hoisted operands passed
  as runtime arguments (realized once per process via the memoized
  ``hoistable_operands``, not once per executable or per batch).

``SKYLARK_NO_PLANS=1`` bypasses everything (the entry points fall back
to the eager path and count a ``bypass``); ``SKYLARK_PLAN_DONATE=0/1``
overrides the backend-based donation default.
"""

from __future__ import annotations

import os
import threading
import time

import jax
import jax.numpy as jnp

from .. import telemetry
from ..sketch.base import Dimension
from ..utils import profiling
from .bucketing import bucket_for, pad_rows
from .cache import PLAN_CACHE

__all__ = [
    "enabled",
    "donation_enabled",
    "fused_enabled",
    "SketchPlan",
    "apply",
    "accumulate_slice",
    "apply_rowwise_bucketed",
    "donating_jit",
    "pad_rows_to_bucket",
    "copy_for_donation",
]


def enabled() -> bool:
    """Plans are on unless ``SKYLARK_NO_PLANS=1`` (checked per call so
    tests and operators can flip it at runtime)."""
    return os.environ.get("SKYLARK_NO_PLANS", "").lower() not in ("1", "true")


def fused_enabled() -> bool:
    """Fused stream-chunk steps (``apply_slice_kernel_acc`` traced as
    the slice-plan body — the accumulator add folds into the sketch
    kernel's emit where the transform supports it) are on unless
    ``SKYLARK_NO_FUSED_CHUNKS=1``.  Checked per call; the flag also
    discriminates the plan key, so flipping it at runtime re-plans
    instead of hitting a stale executable."""
    env = os.environ.get("SKYLARK_NO_FUSED_CHUNKS", "").lower()
    return env not in ("1", "true")


def _kernel_env_token() -> tuple:
    """The env knobs that statically steer which scatter kernel a slice
    trace bakes in (``hash._window_mode``).  Folded into the slice-plan
    key so a runtime flip re-traces rather than serving an executable
    built under the old routing."""
    return (
        os.environ.get("SKYLARK_PALLAS_WINDOW", ""),
        os.environ.get("SKYLARK_NO_PALLAS", "0"),
    )


def donation_enabled() -> bool:
    """Donate accumulator buffers only where XLA honors donation (TPU /
    GPU — CPU silently ignores it); ``SKYLARK_PLAN_DONATE=1/0`` forces."""
    env = os.environ.get("SKYLARK_PLAN_DONATE", "").lower()
    if env in ("1", "true"):
        return True
    if env in ("0", "false"):
        return False
    try:
        backend = jax.default_backend()
    except Exception:  # noqa: BLE001 — no backend, no donation
        return False
    return backend in ("tpu", "gpu", "cuda", "rocm")


def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def _is_sparse(x) -> bool:
    return hasattr(x, "todense")


def _token(S) -> str:
    """The sketch's cache-key identity: its JSON serialization (~100
    bytes, fully determines the counter streams).  Memoized per instance
    — sketches are immutable."""
    tok = S.__dict__.get("_plan_token")
    if tok is None:
        tok = S.__dict__["_plan_token"] = S.to_json()
    return tok


def _sharding_key(x) -> str | None:
    try:
        sh = getattr(x, "sharding", None)
        return None if sh is None else str(sh)
    except Exception:  # noqa: BLE001 — deleted/odd arrays: no sharding key
        return None


class SketchPlan:
    """One compiled apply: a jit-wrapped function plus its counters.

    The trace counter increments inside the traced body (a Python side
    effect runs exactly once per trace), so ``plan.traces`` — and the
    process-wide ``stats()['traces']`` — measure real retraces, not
    calls.  The first call is timed through ``block_until_ready`` as the
    plan's ``compile_seconds`` (trace + XLA compile + first execution).
    """

    def __init__(self, key, fn, donate_argnums: tuple = ()):
        self.key = key
        self.calls = 0
        self.traces = 0
        self.compile_seconds = 0.0
        # First-call accounting must be claimed atomically: two threads
        # racing the same cold plan would otherwise both time the compile
        # and double-bump the process counters.
        self._lock = threading.Lock()

        def traced(*args):
            self.traces += 1
            PLAN_CACHE.bump("traces")
            return fn(*args)

        kw = {"donate_argnums": donate_argnums} if donate_argnums else {}
        self._jit = jax.jit(traced, **kw)

        def uncounted():
            """The same program for ``profiling.records()`` to lower
            again: ``fn`` under the same name, its trace not counted."""
            def traced(*args):
                return fn(*args)

            return jax.jit(traced, **kw)

        self._uncounted = uncounted

    def __call__(self, *args):
        with self._lock:
            first = self.calls == 0
            self.calls += 1
        if first:
            t0 = time.perf_counter()
        if profiling.tracing():
            profiling.note(self._jit, args, {}, lower=self._uncounted)
        out = self._jit(*args)
        if first:
            jax.block_until_ready(out)
            dt = time.perf_counter() - t0
            self.compile_seconds = dt
            PLAN_CACHE.bump("compiles")
            PLAN_CACHE.bump("compile_seconds", dt)
            if telemetry.enabled():
                telemetry.event(
                    "plan", "compile",
                    {"plan": self.key[0], "seconds": round(dt, 6)},
                )
        return out


# -- hoisted-operand flattening ---------------------------------------------
#
# ``hoistable_operands`` returns transform-specific nests mixing arrays
# with static tags (("sign", c, Mi), ((P01, v), ...), a bare Omega, or
# None).  To pass the arrays as runtime jit arguments — so the O(N·S)
# realization is NOT re-run inside (or baked as a constant into) every
# executable — split the nest into a static spec and an array leaf list.


def _split_ops(ops):
    leaves: list = []

    def walk(x):
        if isinstance(x, tuple):
            return ("t", tuple(walk(e) for e in x))
        if hasattr(x, "dtype") and hasattr(x, "shape"):
            leaves.append(x)
            return ("a", len(leaves) - 1)
        return ("s", x)

    return (None, leaves) if ops is None else (walk(ops), leaves)


def _join_ops(spec, leaves):
    if spec is None:
        return None
    tag, val = spec
    if tag == "t":
        return tuple(_join_ops(e, leaves) for e in val)
    if tag == "a":
        return leaves[val]
    return val


def _float_dtype(block):
    dt = block.data.dtype if _is_sparse(block) else block.dtype
    return dt if jnp.issubdtype(dt, jnp.floating) else jnp.dtype(jnp.float32)


# -- the three plan kinds ----------------------------------------------------


def apply(S, A, dim: Dimension | str = Dimension.COLUMNWISE):
    """Plan-cached ``S.apply(A, dim)`` — bitwise identical to eager.

    Falls back to the eager apply (counting a ``bypass``) when plans are
    disabled, when ``A`` is sparse (BCOO applies have data-dependent
    output structure), or when ``A`` is a tracer (the caller's jit
    subsumes the plan).
    """
    dim = Dimension.of(dim)
    if (
        not enabled()
        or _is_sparse(A)
        or _is_tracer(A)
    ):
        PLAN_CACHE.bump("bypasses")
        return S.apply(A, dim)
    from .. import policy

    A = jnp.asarray(A)
    with telemetry.span("plans.lookup"):
        key = (
            "apply",
            _token(S),
            dim.value,
            A.shape,
            A.dtype.name,
            _sharding_key(A),
        )
        policy.note_plan(
            "apply", S, dim=dim.value, shape=A.shape, dtype=A.dtype.name
        )
        plan = PLAN_CACHE.get_or_build(
            key, lambda: SketchPlan(key, lambda A_: S.apply(A_, dim))
        )
    with telemetry.span("sketch.apply", dim=dim.value, shape=A.shape) as sp:
        out = sp.result = plan(A)  # an enabled span blocks on it at exit
    return out


def accumulate_slice(
    S, acc, block, start, *, donate: bool | None = None,
    true_rows: int | None = None, fused: bool | None = None,
):
    """One streaming COLUMNWISE step, planned:
    ``acc + S.apply_slice(block, start)`` (cast to ``acc.dtype``) as a
    single bucketed executable with ``start`` traced and ``acc`` donated.

    The block is zero-padded up to the bucket ladder; the slice kernel
    zeroes any operand window past the sketch domain and padded rows are
    exact zeros, so the padded contribution is exactly 0 and the
    accumulated value matches the eager ``apply_slice`` sum.  A block
    already padded host-side (``pipeline.bucketed_placer``) passes its
    real row count as ``true_rows``.  Falls back to the eager step for
    sparse blocks, transforms without a jit-safe slice kernel, or when
    plans are off.

    ``fused`` (default :func:`fused_enabled`) traces the step through
    ``S.apply_slice_kernel_acc`` — the transform's fused chunk body,
    which for the hash sketches folds the accumulator add into the
    Pallas window kernel's emit (one launch per chunk).  Fused and
    unfused are bitwise identical by the ``apply_slice_kernel_acc``
    contract; ``fused=False`` keeps the explicit two-step composite as
    the operator kill switch (``SKYLARK_NO_FUSED_CHUNKS=1`` process-
    wide, or ``StreamParams(fused_chunks=False)`` per pass).
    """
    k = block.shape[0]
    if (
        not enabled()
        or _is_sparse(block)
        or _is_tracer(block)
        or _is_tracer(acc)
        or not getattr(S, "supports_slice_kernel", False)
        or getattr(block, "ndim", 0) != 2
        or S.n >= 1 << 31
    ):
        PLAN_CACHE.bump("bypasses")
        if true_rows is not None and true_rows != k:
            block = block[:true_rows]
        part = S.apply_slice(block, int(start), Dimension.COLUMNWISE)
        return acc + part.astype(acc.dtype)
    kb = bucket_for(k)
    block = pad_rows(block, kb)
    if donate is None:
        donate = donation_enabled()
    if fused is None:
        fused = fused_enabled()
    block = jnp.asarray(block)
    acc = jnp.asarray(acc)
    key = (
        "slice",
        _token(S),
        (kb,) + tuple(block.shape[1:]),
        block.dtype.name,
        acc.dtype.name,
        _sharding_key(acc),
        bool(donate),
        bool(fused),
        _kernel_env_token(),
    )
    from .. import policy

    policy.note_plan(
        "slice",
        S,
        shape=(kb,) + tuple(block.shape[1:]),
        dtype=block.dtype.name,
        acc_dtype=acc.dtype.name,
    )

    def build():
        if fused:
            def fn(acc_, block_, start_):
                return S.apply_slice_kernel_acc(acc_, block_, start_)
        else:
            def fn(acc_, block_, start_):
                part = S.apply_slice_kernel(block_, start_)
                return acc_ + part.astype(acc_.dtype)

        return SketchPlan(key, fn, donate_argnums=(0,) if donate else ())

    plan = PLAN_CACHE.get_or_build(key, build)
    if telemetry.enabled():
        telemetry.event(
            "plan", "slice", {"bucket": kb, "rows": k, "fused": bool(fused)}
        )
    return plan(acc, block, jnp.asarray(int(start), jnp.int32))


def apply_rowwise_bucketed(
    S, block, *, pad_out: bool = False, true_rows: int | None = None
):
    """One streaming ROWWISE batch, planned: pad the block's example
    rows up to the bucket ladder, apply through one executable per
    bucket (hoisted operands ride as runtime arguments), and return the
    true rows.

    ``pad_out=False`` returns the ``(k, S)`` sketch of the true rows
    (sliced outside the jit) — row-independent applies make every real
    row bitwise equal to the eager ragged apply (bucketing never crosses
    a transform's ``batch_size_gates``, so the algorithm choice matches
    too).  ``pad_out=True`` returns ``(Z_padded, k)`` with the padded
    rows zeroed inside the executable — the fixed-shape form consumers
    feed their own bucketed update plans (the streaming-KRR Gram).
    A block already padded host-side passes its real row count as
    ``true_rows``.
    """
    k = block.shape[0] if true_rows is None else int(true_rows)
    if (
        not enabled()
        or _is_sparse(block)
        or _is_tracer(block)
        or getattr(block, "ndim", 0) != 2
    ):
        PLAN_CACHE.bump("bypasses")
        if k != block.shape[0]:
            block = block[:k]
        ops = S.hoistable_operands(_float_dtype(block))
        Z = S.apply_with_operands(ops, block, Dimension.ROWWISE)
        return (Z, k) if pad_out else Z
    gates = getattr(S, "batch_size_gates", ())
    kb = bucket_for(k, gates)
    if block.shape[0] not in (k, kb):
        # Host-side padding that disagrees with this transform's gates
        # (e.g. a generic placer padding a thin hash batch): recover the
        # real rows and re-bucket under the right gates.
        block = block[:k]
    block = jnp.asarray(pad_rows(block, kb))
    ops = S.hoistable_operands(_float_dtype(block))
    spec, leaves = _split_ops(ops)
    key = (
        "rowwise",
        _token(S),
        block.shape,
        block.dtype.name,
        _sharding_key(block),
        bool(pad_out),
        spec is not None,
    )
    from .. import policy

    policy.note_plan(
        "rowwise", S, shape=block.shape, dtype=block.dtype.name
    )

    def build():
        if pad_out:

            def fn(block_, k_, *op_leaves):
                ops_ = _join_ops(spec, list(op_leaves))
                Z = S.apply_with_operands(ops_, block_, Dimension.ROWWISE)
                valid = jnp.arange(kb) < k_
                return jnp.where(valid[:, None], Z, jnp.zeros((), Z.dtype))

        else:

            def fn(block_, k_, *op_leaves):
                ops_ = _join_ops(spec, list(op_leaves))
                return S.apply_with_operands(ops_, block_, Dimension.ROWWISE)

        return SketchPlan(key, fn)

    plan = PLAN_CACHE.get_or_build(key, build)
    if telemetry.enabled():
        telemetry.event("plan", "rowwise", {"bucket": kb, "rows": k})
    Z = plan(block, jnp.asarray(k, jnp.int32), *leaves)
    if pad_out:
        return Z, k
    return Z if k == kb else Z[:k]


def donating_jit(fn, donate_argnums: tuple = ()):
    """``jax.jit`` with donation applied only where the backend honors it
    (consumers: streaming accumulator updates).  Not plan-cached — jit's
    own shape-keyed cache is the right granularity for ad-hoc updates."""
    if donate_argnums and donation_enabled():
        return jax.jit(fn, donate_argnums=donate_argnums)
    return jax.jit(fn)


def pad_rows_to_bucket(block, gates: tuple = ()):
    """Convenience: ``(padded_block, true_rows)`` on the ladder."""
    k = int(block.shape[0])
    return pad_rows(block, bucket_for(k, gates)), k


def copy_for_donation(tree):
    """Device-copy every jax array leaf — used by consumers that must
    keep a pre-donation snapshot alive (the streaming engine's chunk-
    entry state, which the divergence guard may still read)."""
    def _copy(x):
        if isinstance(x, jax.Array) and not _is_tracer(x):
            return jnp.array(x, copy=True)
        return x

    return jax.tree_util.tree_map(_copy, tree)
