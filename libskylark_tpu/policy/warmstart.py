"""Warm start: replay the profile's hot plan keys before first traffic.

A fresh process pays the full trace + XLA-compile cost for every plan
its predecessor already measured (``plans.stats()["compile_seconds"]``
— recorded in the profile store's meta block).  ``warm_start()``
collapses that cold start by **plan replay**: it reconstructs the
store's hottest (sketch, signature) keys (``SketchTransform.from_json``
+ a zeros array of the recorded abstract shape) and pushes them through
the live plan entry points, so the process-wide ``PlanCache`` holds the
traced executables before the first real request arrives.  The replayed
plans compile through whatever persistent XLA cache the process has
(``utils.compile_cache.place`` — warm start never moves it; it only
reports the directory in its summary).

Replays are firewalled per key: a stale record (sketch type renamed,
shape no longer valid) is skipped and counted, never fatal.
"""

from __future__ import annotations

import time

from . import config
from .profile import load_entries

__all__ = ["warm_start"]


def _replay_one(rec: dict) -> bool:
    import jax.numpy as jnp

    from .. import plans
    from ..sketch.base import from_json

    S = from_json(rec["sketch"])
    kind = rec.get("plan")
    shape = tuple(int(v) for v in rec.get("shape") or ())
    dtype = jnp.dtype(rec.get("dtype") or "float32")
    if kind == "apply":
        plans.apply(S, jnp.zeros(shape, dtype), rec.get("dim") or "columnwise")
    elif kind == "slice":
        acc_dtype = jnp.dtype(rec.get("acc_dtype") or "float32")
        acc = jnp.zeros((S.s, shape[1]), acc_dtype)
        plans.accumulate_slice(S, acc, jnp.zeros(shape, dtype), 0)
    elif kind == "rowwise":
        plans.apply_rowwise_bucketed(S, jnp.zeros(shape, dtype))
    else:
        return False
    return True


def warm_start(
    directory: str | None = None, *, max_plans: int | None = None
) -> dict:
    """Prime the process from the profile store; returns a summary dict
    ``{"enabled", "profile_keys", "plans_replayed", "plans_skipped",
    "xla_cache_dir", "seconds"}``.

    Safe to call unconditionally at process start (the CLIs do, under
    ``--policy``): disabled or storeless it returns immediately."""
    summary = {
        "enabled": False,
        "profile_keys": 0,
        "plans_replayed": 0,
        "plans_skipped": 0,
        "xla_cache_dir": None,
        "seconds": 0.0,
    }
    if not config.enabled():
        return summary
    directory = directory or config.policy_dir()
    if not directory:
        return summary
    view = load_entries(directory)
    if view is None or not view.get("files"):
        # No predecessor left a store here: nothing to replay.
        return summary
    t0 = time.perf_counter()
    summary["enabled"] = True
    summary["profile_keys"] = len(view.get("entries", {}))
    import jax

    summary["xla_cache_dir"] = jax.config.jax_compilation_cache_dir
    budget = config.warm_plans() if max_plans is None else max(0, max_plans)
    for rec in (view.get("plans") or [])[:budget]:
        try:
            ok = _replay_one(rec)
        except Exception:  # noqa: BLE001 — stale record: skip, not fatal
            ok = False
        summary["plans_replayed" if ok else "plans_skipped"] += 1
    summary["seconds"] = round(time.perf_counter() - t0, 6)
    from .. import telemetry

    if telemetry.enabled():
        telemetry.inc("policy.warm_plans", summary["plans_replayed"])
        telemetry.event("policy", "warm_start", dict(summary))
    return summary
