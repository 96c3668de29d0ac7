"""``choose_route``: the pure decision function of the policy layer.

≙ the reference's ``algorithms/`` regression dispatch — problems carry
tags and the library picks the solver specialization — upgraded to
decide from *measured* evidence: the profile store's per-(backend,
dtype, shape-class) summaries of what the guard, the plan cache, and
the streaming engine observed on earlier runs.

Decision contract (the elastic-world invariant): a decision is a pure
function of ``(profile entry, problem signature, pinned overrides)`` —
no RNG, no clocks, no per-rank state — so every process of a
``jax.distributed`` world reading the same store files computes the
identical decision.  And the empty-store decision IS the historical
default (same sketch family, same ``min(4n, m)`` dimension, same route,
same dtype), so attempt 0 with nothing learned is bitwise identical to
the pre-policy library.

What a matured entry can change:

- **route** — repeated dense fallbacks mean the sketch route keeps
  failing on this shape class: go straight to the exact solve.
  Repeated RESKETCH verdicts mean the problems are ill-conditioned but
  recoverable: route to the preconditioned iterative solvers
  (Blendenpik dense / LSRN sparse), whose whole design point is
  near-machine-precision on exactly those problems.
- **sketch dimension** — the recorded certificates are short-budget
  ``cond_est`` evidence; a history of comfortable margins shrinks the
  dimension toward the smallest size that certified OK (and probes one
  step below it), with the guard ladder as the safety net when the
  probe undershoots.
- **route (refine)** — once the key has refinement history on record —
  at least one certified-converged refine run and no recorded
  stagnation — AND a comfortable cond margin, healthy entries earn the
  ``refine`` route: certified mixed-precision refinement reaches
  near-machine accuracy at a fraction of the exact-f64 flops.  A single
  recorded stagnation retires the route (the history requirement fails)
  until the key's refine record is clean again.
- **precision** — bf16-first on MXU backends once the entry is healthy
  and no bf16 failure is on record; the guard certificate checks the
  narrow sketch and the caller escalates back to the input dtype on a
  RESKETCH verdict (the ``f32_accumulable`` kernel entry points make
  the narrow attempt nearly free).  The ladder is f32 → bf16 and stops
  there.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from . import config
from .profile import load_entries, profile_key

__all__ = ["ProblemSignature", "Decision", "choose_route"]

# Valid least-squares routes, in escalation order of cost.
LS_ROUTES = ("sketch", "refine", "blendenpik", "lsrn", "exact")

# A certificate is "comfortable" when the estimated cond sits at least
# this factor under the guard ceiling — margin enough that a smaller
# sketch (cond grows as the dimension shrinks toward n) stays certified.
# The f32 ceiling is 0.1/sqrt(eps) ≈ 290, so the factor must leave room
# for healthy sketches (cond of a few) to qualify.
_COMFORT_MARGIN = 16.0


@dataclass(frozen=True)
class ProblemSignature:
    """What the dispatcher is allowed to see of a problem: its tags."""

    kind: str  # "ls" | "ls_stream" | "krr" | "train"
    m: int
    n: int
    targets: int = 1
    dtype: str = "float32"
    sparse: bool = False
    backend: str = "cpu"

    @property
    def key(self) -> str:
        return profile_key(
            self.kind, self.backend, self.dtype, self.m, self.n
        )


@dataclass
class Decision:
    """One routing decision plus its provenance (``info["policy"]``)."""

    route: str
    sketch_type: str
    sketch_size: int
    compute_dtype: str | None = None
    source: str = "default"  # default | profile
    key: str = ""
    escalated: bool = False
    reasons: list = field(default_factory=list)

    def to_dict(self) -> dict:
        d = {
            "route": self.route,
            "sketch_type": self.sketch_type,
            "sketch_size": int(self.sketch_size),
            "source": self.source,
            "key": self.key,
        }
        if self.compute_dtype:
            d["compute_dtype"] = self.compute_dtype
        if self.escalated:
            d["escalated"] = True
        if self.reasons:
            d["reasons"] = list(self.reasons)
        return d


def _default_decision(sig: ProblemSignature) -> Decision:
    """The historical defaults, exactly (bit-parity anchor)."""
    if sig.kind == "ls":
        stype = "CWT" if sig.sparse else "FJLT"
        s = min(4 * sig.n, sig.m)
        return Decision("sketch", stype, s, key=sig.key)
    if sig.kind == "ls_stream":
        stype = "CWT" if sig.sparse else "JLT"
        s = min(4 * sig.n, sig.m)
        return Decision("sketch", stype, s, key=sig.key)
    if sig.kind == "krr":
        # n is the feature count the caller fixed; the route is the
        # Cholesky normal-equations solve.  Only precision is decidable.
        return Decision("cholesky", "-", sig.n, key=sig.key)
    if sig.kind == "train":
        # n is the total random-feature count the trainer's maps fixed;
        # the route is the BlockADMM consensus trainer.  Only the
        # precision rung is decidable.
        return Decision("admm", "-", sig.n, key=sig.key)
    raise ValueError(f"unknown problem kind {sig.kind!r}")


def _cond_ceiling(dtype: str) -> float:
    from ..guard import config as guard_config

    try:
        return float(guard_config.cond_max(dtype))
    except TypeError:
        return float(guard_config.cond_max())


def _healthy(entry: dict) -> bool:
    g = entry.get("guard") or {}
    return (
        int(g.get("fallback", 0)) == 0 and int(g.get("resketch", 0)) == 0
    )


def choose_route(
    sig: ProblemSignature,
    *,
    route: str | None = None,
    sketch_type: str | None = None,
    sketch_size: int | None = None,
    guard_on: bool = True,
    store_view: dict | None = None,
) -> Decision:
    """Decide (route, sketch family + dimension, precision) for ``sig``.

    Explicit overrides win unconditionally: a caller-pinned ``route`` /
    ``sketch_type`` / ``sketch_size`` is honored verbatim and the policy
    only fills the fields left open.  With the layer disabled, the store
    empty, the entry immature (< ``SKYLARK_POLICY_MIN_SAMPLES`` runs),
    or guarding off (deviations lean on certification as the safety
    net), the decision is exactly the historical default.
    """
    d = _default_decision(sig)
    if route is not None:
        d.route = route
        d.reasons.append("route pinned by caller")
    if sketch_type is not None:
        d.sketch_type = sketch_type
    if sketch_size is not None:
        d.sketch_size = int(sketch_size)
    if not config.enabled() or not guard_on:
        return d
    view = store_view if store_view is not None else load_entries()
    entry = (view or {}).get("entries", {}).get(sig.key)
    from .. import telemetry

    if entry is None or int(entry.get("runs", 0)) < config.min_samples():
        telemetry.inc("policy.profile_misses")
        return d
    telemetry.inc("policy.profile_hits")
    d.source = "profile"
    runs = max(1, int(entry.get("runs", 1)))
    g = entry.get("guard") or {}
    fallback_rate = int(g.get("fallback", 0)) / runs
    resketch_rate = int(g.get("resketch", 0)) / runs
    healthy = _healthy(entry)

    # -- route ---------------------------------------------------------------
    if route is None and sig.kind == "ls":
        if fallback_rate >= 0.5:
            d.route = "exact"
            d.reasons.append(
                f"fallback rate {fallback_rate:.2f}: sketching keeps "
                "failing on this shape class"
            )
        elif resketch_rate >= 0.5:
            d.route = "lsrn" if sig.sparse else "blendenpik"
            d.reasons.append(
                f"resketch rate {resketch_rate:.2f}: ill-conditioned but "
                "recoverable; preconditioned iterative route"
            )
        else:
            # The refine route must be EARNED through recorded refine
            # history (an "auto" caller never lands here cold): at least
            # one certified-converged run, zero recorded stagnations —
            # a single stagnation retires the route — plus a healthy
            # guard record and a comfortable cond margin so the
            # low-precision factorization has headroom.
            rf = entry.get("refine") or {}
            cond_seen = (entry.get("cond") or {}).get("max")
            if (
                healthy
                and int(rf.get("ok", 0)) >= 1
                and int(rf.get("stagnate", 0)) == 0
                and cond_seen is not None
                and float(cond_seen) * _COMFORT_MARGIN
                < _cond_ceiling(sig.dtype)
            ):
                d.route = "refine"
                d.reasons.append(
                    f"refine earned: {int(rf.get('ok', 0))} certified "
                    "refine runs, no stagnation, comfortable cond margin"
                )

    # -- sketch dimension ----------------------------------------------------
    if (
        sketch_size is None
        and d.route == "sketch"
        and sig.kind in ("ls", "ls_stream")
        and healthy
    ):
        sk = entry.get("sketch") or {}
        cond = entry.get("cond") or {}
        floor = min(2 * sig.n, sig.m)
        target = d.sketch_size
        if sk.get("min_ok") is not None:
            target = min(target, int(sk["min_ok"]))
        cond_max_seen = cond.get("max")
        if (
            cond_max_seen is not None
            and float(cond_max_seen) * _COMFORT_MARGIN
            < _cond_ceiling(sig.dtype)
        ):
            # Comfortable margin: probe one geometric step below the
            # smallest certified size.  The runtime certificate (the
            # short-budget cond_est the guard runs on every attempt 0)
            # validates the probe; an undershoot climbs the grow rung
            # and the recorded RESKETCH retires further shrinks.
            target = (target * 3) // 4
            d.reasons.append(
                f"cond margin {float(cond_max_seen):.3e} ≪ ceiling: "
                "probing a smaller sketch dimension"
            )
        new_s = max(floor, min(d.sketch_size, target))
        if new_s != d.sketch_size:
            d.sketch_size = int(new_s)
            if not d.reasons or "probing" not in d.reasons[-1]:
                d.reasons.append("shrunk to smallest certified-OK dimension")

    # -- precision -----------------------------------------------------------
    bf = entry.get("bf16") or {}
    if (
        sig.dtype == "float32"
        and not sig.sparse
        and sig.kind in ("ls", "krr", "train")
        and d.route != "refine"  # refine owns its precision rung
        and healthy
        and int(bf.get("fail", 0)) == 0
        and config.bf16_allowed(sig.backend)
    ):
        d.compute_dtype = "bfloat16"
        d.reasons.append(
            "bf16-first: healthy entry, no bf16 failure on record; guard "
            "certifies, f32 is the escalation rung"
        )
    return d
