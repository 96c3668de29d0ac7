"""Observation recording: solve outcomes and hot plan keys → the store.

The flow mirrors the telemetry ledger's lifecycle: routed entrypoints
call :func:`consult` before the solve and :func:`observe` after it
(queuing an observation in memory), the plan layer calls
:func:`note_plan` on every planned apply (counting hot keys), and the
terminal ``telemetry.run_summary`` of the run calls :func:`flush` —
which folds everything pending into this process's profile file.  With
the layer disabled or no ``SKYLARK_POLICY_DIR`` configured, every one
of these is an allocation-free early return.
"""

from __future__ import annotations

import threading
import time

from . import config
from .decide import Decision, ProblemSignature, choose_route
from .profile import ProfileStore

__all__ = [
    "consult",
    "observe",
    "note_plan",
    "flush",
    "recording_active",
    "reset",
]

_LOCK = threading.RLock()
_STATE = {"store": None, "pending": 0}


def recording_active() -> bool:
    """True when observations will actually be persisted."""
    return config.enabled() and config.policy_dir() is not None


def _store() -> ProfileStore:
    with _LOCK:
        st = _STATE["store"]
        directory = config.policy_dir()
        if st is None or st.directory != directory:
            st = ProfileStore(directory)
            _STATE["store"] = st
        return st


def reset() -> None:
    """Drop pending state (test hook; nothing on disk is touched)."""
    with _LOCK:
        _STATE["store"] = None
        _STATE["pending"] = 0


def _backend() -> str:
    try:
        import jax

        return str(jax.default_backend())
    except Exception:  # noqa: BLE001 — no backend: profile under "cpu"
        return "cpu"


def consult(
    kind: str,
    *,
    m: int,
    n: int,
    targets: int = 1,
    dtype,
    sparse: bool = False,
    route: str | None = None,
    sketch_type: str | None = None,
    sketch_size: int | None = None,
    guard_on: bool = True,
) -> Decision:
    """Build the signature and run :func:`~libskylark_tpu.policy.
    choose_route`; the one call every routed entrypoint makes."""
    sig = ProblemSignature(
        kind=kind,
        m=int(m),
        n=int(n),
        targets=int(targets),
        dtype=str(dtype),
        sparse=bool(sparse),
        backend=_backend(),
    )
    d = choose_route(
        sig,
        route=route,
        sketch_type=sketch_type,
        sketch_size=sketch_size,
        guard_on=guard_on,
    )
    from .. import telemetry

    telemetry.inc("policy.decisions")
    # route + provenance onto any serve trace this solve is answering
    telemetry.trace_event(
        "policy",
        route=d.route,
        sketch_type=d.sketch_type,
        sketch_size=int(d.sketch_size),
        source=d.source,
        escalated=d.escalated,
        reasons=list(d.reasons),
    )
    if d.route not in ("sketch", "cholesky"):
        telemetry.inc(f"policy.route.{d.route}")
    if d.compute_dtype:
        telemetry.inc("policy.bf16_first")
    return d


def _recovery_obs(info: dict | None) -> dict:
    """Fold ``info["recovery"]`` into observation fields."""
    obs: dict = {}
    rec = (info or {}).get("recovery") or {}
    attempts = rec.get("attempts") or []
    if not rec.get("guarded", False):
        return obs
    if attempts:
        first = attempts[0]
        obs["ok0"] = first.get("verdict") == "OK"
        obs["resketches"] = sum(
            1 for a in attempts if a.get("verdict") == "RESKETCH"
        )
        obs["fallback"] = any(
            a.get("action") == "fallback" or a.get("verdict") == "FALLBACK"
            for a in attempts
        )
        for a in attempts:
            if a.get("verdict") == "OK":
                if a.get("cond") is not None:
                    obs["cond"] = a["cond"]
                if a.get("sketch_size") is not None:
                    obs["sketch_size"] = a["sketch_size"]
                break
    return obs


def observe(
    decision: Decision,
    info: dict | None,
    *,
    default_size: int | None = None,
    bf16: str | None = None,
    refine: dict | None = None,
    rows_per_s: float | None = None,
    batches: int | None = None,
) -> None:
    """Queue one run observation (persisted by the next :func:`flush`)."""
    if not recording_active() or not decision.key:
        return
    obs = _recovery_obs(info)
    obs["route"] = decision.route
    obs["sketch_type"] = decision.sketch_type
    if refine is not None:
        obs["refine"] = dict(refine)
    if default_size is not None:
        obs["default_size"] = int(default_size)
    if decision.escalated:
        obs["escalated"] = True
    if bf16 is not None:
        obs["bf16"] = bf16
    elif decision.compute_dtype == "bfloat16":
        obs["bf16"] = "ok" if obs.get("ok0", True) else "fail"
    if rows_per_s is not None:
        obs["rows_per_s"] = rows_per_s
        obs["batches"] = int(batches or 0)
    with _LOCK:
        _store().fold(decision.key, obs, now=time.time())
        _STATE["pending"] += 1
    from .. import telemetry

    if decision.escalated:
        telemetry.inc("policy.escalations")


def note_plan(
    plan: str,
    S,
    *,
    dim: str | None = None,
    shape=None,
    dtype: str | None = None,
    acc_dtype: str | None = None,
) -> None:
    """Count one plan-cache key toward the store's hot-plan replay list.

    Called from the plan layer on every planned apply; the record keeps
    exactly what the warm start needs to replay the trace — the sketch
    JSON plus the abstract input signature."""
    if not recording_active():
        return
    try:
        rec = {
            "plan": plan,
            "sketch": S.to_json(),
            "dim": dim,
            "shape": list(shape) if shape is not None else None,
            "dtype": dtype,
            "acc_dtype": acc_dtype,
        }
    except Exception:  # noqa: BLE001 — unserializable sketch: skip
        return
    with _LOCK:
        _store().note_plan(rec)
        _STATE["pending"] += 1


def flush(name: str | None = None, info: dict | None = None) -> str | None:
    """Persist pending observations (the ``run_summary``-time write).

    Called by ``telemetry.run_summary`` before its own enabled gate, so
    profiles persist even with telemetry off.  Also records the
    plan-cache compile totals for the cold-vs-warm accounting."""
    if not recording_active():
        return None
    with _LOCK:
        if _STATE["pending"] == 0:
            return None
        store = _store()
        from .. import plans

        st = plans.stats()
        store.set_meta(
            plan_compiles=st["compiles"],
            plan_compile_seconds=st["compile_seconds"],
        )
        path = store.save(now=time.time())
        if path is not None:
            _STATE["pending"] = 0
        from .. import telemetry

        telemetry.inc("policy.profile_writes")
        return path
