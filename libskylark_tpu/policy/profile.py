"""The persistent profile store: per-(backend, dtype, shape-class) summaries.

One JSON file per writer process — ``profile-<pid>.json`` under
``SKYLARK_POLICY_DIR`` — mirroring the telemetry run-ledger discipline
(``ledger-<pid>.jsonl``): multi-process jobs never interleave writers,
and a reader merges every file it can parse.  Each file carries a CRC32
over its canonical payload so a torn write (preempted mid-``rename``,
dead filesystem, byte flip) is *skipped*, never half-trusted; merging is
last-writer-wins per profile key on the entry's ``updated`` timestamp
(ties broken by pid then filename, so every rank of a world computes the
identical merged view from the same files).

Entry schema (one per :func:`profile_key`):

.. code-block:: json

    {"runs": 7, "updated": 1754000000.0,
     "guard": {"ok": 6, "resketch": 1, "fallback": 0},
     "cond": {"last": 1.2e3, "max": 4.1e3},
     "sketch": {"type": "FJLT", "min_ok": 512, "default": 2048},
     "bf16": {"ok": 3, "fail": 0},
     "refine": {"ok": 2, "stagnate": 0, "iters": 47, "rung": "bf16+f32"},
     "routes": {"sketch": 7},
     "escalations": 0,
     "throughput": {"rows_per_s": 1.1e6, "batches": 16}}

plus a store-level ``plans`` list of hot plan-cache keys (sketch JSON +
abstract input signature — enough to replay the trace at warm start) and
a ``meta`` block (plan-cache compile totals).
"""

from __future__ import annotations

import json
import math
import os
import threading
import zlib

from . import config

__all__ = [
    "shape_class",
    "profile_key",
    "ProfileStore",
    "load_entries",
    "invalidate_cache",
]

SCHEMA_VERSION = 1
# Hot-plan records kept per store file (the warm-start replay budget is
# the separate SKYLARK_POLICY_WARM_PLANS read knob).
MAX_PLAN_RECORDS = 32

_LOCK = threading.RLock()

# Merged-view cache keyed by directory; invalidated by (name, mtime_ns,
# size) stat signatures so sweeps don't re-parse the store per solve.
_CACHE: dict = {}


def shape_class(m: int, n: int) -> str:
    """Geometric shape bucket ``r<ceil log2 m>c<ceil log2 n>`` — the same
    power-of-two ladder the plan layer buckets batches on, so problems
    that share executables share profile entries."""

    def _l2(x: int) -> int:
        return max(0, math.ceil(math.log2(max(int(x), 1))))

    return f"r{_l2(m)}c{_l2(n)}"


def profile_key(kind: str, backend: str, dtype: str, m: int, n: int) -> str:
    """The store key: ``kind|backend|dtype|shape-class``."""
    return "|".join([kind, backend, str(dtype), shape_class(m, n)])


def _crc(payload: dict) -> int:
    return zlib.crc32(
        json.dumps(payload, sort_keys=True, separators=(",", ":")).encode()
    ) & 0xFFFFFFFF


def _read_file(path: str):
    """Parse one store file; None on any corruption (torn JSON, CRC
    mismatch, wrong version) — the caller counts and skips."""
    try:
        with open(path, "rb") as fh:
            doc = json.loads(fh.read().decode("utf-8"))
    except (OSError, ValueError, UnicodeDecodeError):
        return None
    if not isinstance(doc, dict) or doc.get("version") != SCHEMA_VERSION:
        return None
    payload = doc.get("payload")
    if not isinstance(payload, dict) or doc.get("crc") != _crc(payload):
        return None
    return doc


def _merge_files(directory: str) -> dict:
    """Merged view of every parseable ``profile-*.json`` in the dir."""
    entries: dict = {}
    wins: dict = {}  # key -> (updated, pid, fname) of the current winner
    plans: dict = {}  # record-key -> {"count": n, ...record}
    meta: dict = {}
    meta_win = (-1.0, -1, "")
    corrupt = 0
    try:
        names = sorted(
            f for f in os.listdir(directory)
            if f.startswith("profile-") and f.endswith(".json")
        )
    except OSError:
        names = []
    for fname in names:
        doc = _read_file(os.path.join(directory, fname))
        if doc is None:
            corrupt += 1
            continue
        payload = doc["payload"]
        pid = int(doc.get("pid", 0))
        for key, entry in (payload.get("entries") or {}).items():
            if not isinstance(entry, dict):
                continue
            mark = (float(entry.get("updated", 0.0)), pid, fname)
            if key not in entries or mark > wins[key]:
                entries[key] = entry
                wins[key] = mark
        for rec in payload.get("plans") or []:
            if not isinstance(rec, dict):
                continue
            rk = _plan_record_key(rec)
            if rk in plans:
                plans[rk]["count"] += int(rec.get("count", 1))
            else:
                plans[rk] = dict(rec, count=int(rec.get("count", 1)))
        fmeta = payload.get("meta") or {}
        mark = (float(fmeta.get("updated", 0.0)), pid, fname)
        if fmeta and mark > meta_win:
            meta = fmeta
            meta_win = mark
    return {
        "entries": entries,
        "plans": sorted(
            plans.values(), key=lambda r: (-r["count"], _plan_record_key(r))
        ),
        "meta": meta,
        "corrupt_files": corrupt,
        "files": len(names),
    }


def _plan_record_key(rec: dict) -> str:
    return "|".join(
        str(rec.get(k))
        for k in ("plan", "sketch", "dim", "shape", "dtype", "acc_dtype")
    )


def _stat_signature(directory: str):
    try:
        names = sorted(
            f for f in os.listdir(directory)
            if f.startswith("profile-") and f.endswith(".json")
        )
    except OSError:
        return ()
    sig = []
    for f in names:
        try:
            st = os.stat(os.path.join(directory, f))
            sig.append((f, st.st_mtime_ns, st.st_size))
        except OSError:
            sig.append((f, -1, -1))
    return tuple(sig)


def load_entries(directory: str | None = None) -> dict | None:
    """The merged store view (cached by file stats); None with no dir."""
    directory = directory or config.policy_dir()
    if not directory:
        return None
    with _LOCK:
        sig = _stat_signature(directory)
        cached = _CACHE.get(directory)
        if cached is not None and cached[0] == sig:
            return cached[1]
        view = _merge_files(directory)
        _CACHE[directory] = (sig, view)
        return view


def invalidate_cache() -> None:
    """Drop the merged-view cache (test hook; reads re-stat anyway)."""
    with _LOCK:
        _CACHE.clear()


class ProfileStore:
    """This process's own profile file plus the merged read view.

    Writers fold observations into the in-memory pending state
    (:meth:`fold`, :meth:`note_plan`) and :meth:`save` rewrites
    ``profile-<pid>.json`` atomically (tmp + fsync + rename) with the
    CRC over the canonical payload.  The pending state is seeded from
    the merged view per key on first fold, so one process's file carries
    forward what previous processes learned (last-writer-wins keeps the
    newest file authoritative either way).
    """

    def __init__(self, directory: str | None = None):
        self.directory = directory or config.policy_dir()
        self._entries: dict = {}
        self._plans: dict = {}
        self._meta: dict = {}
        self._dirty = False

    # -- folding ------------------------------------------------------------

    def _seed(self, key: str) -> dict:
        entry = self._entries.get(key)
        if entry is None:
            view = load_entries(self.directory)
            merged = (view or {}).get("entries", {}).get(key)
            entry = json.loads(json.dumps(merged)) if merged else {
                "runs": 0,
                "guard": {"ok": 0, "resketch": 0, "fallback": 0},
                "cond": {"last": None, "max": None},
                "sketch": {"type": None, "min_ok": None, "default": None},
                "bf16": {"ok": 0, "fail": 0},
                "routes": {},
                "escalations": 0,
            }
            self._entries[key] = entry
        return entry

    def fold(self, key: str, obs: dict, *, now: float) -> None:
        """Merge one run observation into the pending entry for ``key``.

        ``obs`` fields (all optional): ``ok0`` (attempt-0 certificate
        OK), ``resketches``, ``fallback``, ``cond``, ``sketch_type``,
        ``sketch_size`` (certified-OK size), ``default_size``, ``route``,
        ``bf16`` (``"ok"``/``"fail"``), ``refine`` (the solve's
        ``info["refine"]`` dict: ``converged``/``iters``/``rung``),
        ``escalated``, ``rows_per_s``, ``batches``.
        """
        with _LOCK:
            e = self._seed(key)
            e["runs"] = int(e.get("runs", 0)) + 1
            e["updated"] = float(now)
            g = e.setdefault(
                "guard", {"ok": 0, "resketch": 0, "fallback": 0}
            )
            if obs.get("ok0"):
                g["ok"] = g.get("ok", 0) + 1
            g["resketch"] = g.get("resketch", 0) + int(
                obs.get("resketches", 0)
            )
            if obs.get("fallback"):
                g["fallback"] = g.get("fallback", 0) + 1
            cond = obs.get("cond")
            if cond is not None and math.isfinite(float(cond)):
                c = e.setdefault("cond", {"last": None, "max": None})
                c["last"] = float(cond)
                c["max"] = (
                    float(cond)
                    if c.get("max") is None
                    else max(float(c["max"]), float(cond))
                )
            sk = e.setdefault(
                "sketch", {"type": None, "min_ok": None, "default": None}
            )
            if obs.get("sketch_type"):
                sk["type"] = obs["sketch_type"]
            if obs.get("default_size") is not None:
                sk["default"] = int(obs["default_size"])
            if obs.get("sketch_size") is not None:
                s_ok = int(obs["sketch_size"])
                sk["min_ok"] = (
                    s_ok
                    if sk.get("min_ok") is None
                    else min(int(sk["min_ok"]), s_ok)
                )
            if obs.get("route"):
                r = e.setdefault("routes", {})
                r[obs["route"]] = r.get(obs["route"], 0) + 1
            if obs.get("bf16") in ("ok", "fail"):
                b = e.setdefault("bf16", {"ok": 0, "fail": 0})
                b[obs["bf16"]] = b.get(obs["bf16"], 0) + 1
            rf_obs = obs.get("refine")
            if isinstance(rf_obs, dict) and rf_obs.get("converged") is not None:
                rf = e.setdefault(
                    "refine",
                    {"ok": 0, "stagnate": 0, "iters": None, "rung": None},
                )
                # A non-converged final state means refinement stagnated
                # (or fell through the ladder to the exact fallback) —
                # either way the route's premise failed for this key.
                which = "ok" if rf_obs.get("converged") else "stagnate"
                rf[which] = int(rf.get(which, 0)) + 1
                if rf_obs.get("iters") is not None:
                    rf["iters"] = int(rf_obs["iters"])
                if rf_obs.get("rung"):
                    rf["rung"] = str(rf_obs["rung"])
            if obs.get("escalated"):
                e["escalations"] = int(e.get("escalations", 0)) + 1
            if obs.get("rows_per_s") is not None:
                e["throughput"] = {
                    "rows_per_s": round(float(obs["rows_per_s"]), 3),
                    "batches": int(obs.get("batches", 0)),
                }
            self._dirty = True

    def note_plan(self, rec: dict) -> None:
        """Count one plan-cache key toward the hot-plan list."""
        with _LOCK:
            rk = _plan_record_key(rec)
            if rk in self._plans:
                self._plans[rk]["count"] += 1
            else:
                self._plans[rk] = dict(rec, count=1)
            self._dirty = True

    def set_meta(self, **kv) -> None:
        with _LOCK:
            self._meta.update({k: v for k, v in kv.items() if v is not None})
            self._dirty = True

    # -- persistence --------------------------------------------------------

    def save(self, *, now: float) -> str | None:
        """Atomically rewrite this process's profile file; returns its
        path (None when no directory is configured or nothing pending)."""
        with _LOCK:
            if not self.directory or not self._dirty:
                return None
            # Carry forward previously-merged hot plans so a short-lived
            # process does not erase a long-lived one's replay list.
            view = load_entries(self.directory) or {}
            plans = {
                _plan_record_key(r): dict(r) for r in view.get("plans", [])
            }
            for rk, rec in self._plans.items():
                if rk in plans:
                    plans[rk]["count"] = max(
                        int(plans[rk].get("count", 0)), int(rec["count"])
                    )
                else:
                    plans[rk] = dict(rec)
            top = sorted(
                plans.values(), key=lambda r: (-r["count"], _plan_record_key(r))
            )[:MAX_PLAN_RECORDS]
            meta = dict(view.get("meta") or {})
            meta.update(self._meta)
            meta["updated"] = float(now)
            payload = {
                "entries": self._entries,
                "plans": top,
                "meta": meta,
            }
            doc = {
                "version": SCHEMA_VERSION,
                "pid": os.getpid(),
                "payload": payload,
                "crc": _crc(payload),
            }
            os.makedirs(self.directory, exist_ok=True)
            path = os.path.join(
                self.directory, f"profile-{os.getpid()}.json"
            )
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump(doc, fh, sort_keys=True)
                fh.flush()
                os.fsync(fh.fileno())
            os.replace(tmp, path)
            self._dirty = False
            invalidate_cache()
            return path
