"""Runtime knobs of the adaptive execution policy layer.

All knobs are environment variables read PER CALL (the established
``SKYLARK_GUARD`` / ``SKYLARK_TELEMETRY`` discipline) so tests and
operators can flip them at runtime:

- ``SKYLARK_POLICY`` — ``0``/``false`` disables the policy layer
  entirely: no profile reads, no routing, no warm start; every routed
  entrypoint behaves exactly like the pre-policy library.  Default ON —
  but with no profile store configured (and on every key the store has
  not matured for) the decisions are bitwise identical to the historical
  defaults, so "on with nothing learned" is indistinguishable from off.
- ``SKYLARK_POLICY_DIR`` — directory of the JSON profile store
  (``profile-<pid>.json`` per writer, merged last-writer-wins on read).
  Unset: decisions stay default and nothing is ever written.
- ``SKYLARK_POLICY_MIN_SAMPLES`` — observed runs a (backend, dtype,
  shape-class) key needs before decisions may deviate from the defaults
  (default 3: one run proves nothing about the randomness).
- ``SKYLARK_POLICY_WARM_PLANS`` — hot plan keys replayed through the
  plan cache by :func:`~libskylark_tpu.policy.warm_start` (default 8).
- ``SKYLARK_POLICY_BF16`` — ``1`` force-allows the bf16-first precision
  rung on any backend (CPU tests), ``0`` force-denies it; unset, bf16 is
  considered only on MXU backends (tpu/gpu) where the
  ``f32_accumulable`` kernel entry points make it cheap.
"""

from __future__ import annotations

import os

__all__ = [
    "enabled",
    "policy_dir",
    "configure",
    "min_samples",
    "warm_plans",
    "bf16_allowed",
]

# configure() override; None defers to SKYLARK_POLICY_DIR.
_DIR_OVERRIDE: list = [None]


def enabled() -> bool:
    """Policy is on unless ``SKYLARK_POLICY=0`` (checked per call)."""
    return os.environ.get("SKYLARK_POLICY", "").lower() not in ("0", "false")


def policy_dir() -> str | None:
    """The profile-store directory (``configure()`` wins over the env)."""
    if _DIR_OVERRIDE[0] is not None:
        return _DIR_OVERRIDE[0]
    return os.environ.get("SKYLARK_POLICY_DIR") or None


def configure(directory) -> None:
    """Point the profile store at ``directory`` (overrides
    ``SKYLARK_POLICY_DIR``; ``None`` reverts to the env knob)."""
    _DIR_OVERRIDE[0] = str(directory) if directory else None


def min_samples(default: int = 3) -> int:
    """Runs a profile key needs before decisions deviate (≥ 1)."""
    raw = os.environ.get("SKYLARK_POLICY_MIN_SAMPLES")
    if raw is None:
        return default
    try:
        return max(1, int(raw))
    except ValueError:
        return default


def warm_plans(default: int = 8) -> int:
    """Hot plan keys ``warm_start`` replays (0 disables the replay)."""
    raw = os.environ.get("SKYLARK_POLICY_WARM_PLANS")
    if raw is None:
        return default
    try:
        return max(0, int(raw))
    except ValueError:
        return default


def bf16_allowed(backend: str) -> bool:
    """May the precision rung propose bf16-first on ``backend``?"""
    raw = os.environ.get("SKYLARK_POLICY_BF16")
    if raw is not None:
        return raw.lower() not in ("0", "false", "")
    return backend in ("tpu", "gpu", "cuda", "rocm")
