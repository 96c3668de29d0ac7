"""Adaptive execution policy (ISSUE PR 9): profile store, routing
decisions, warm start, and the bit-parity contract.

All tests run under the ``policy`` marker (tier-1, 120 s per-test
alarm).  The load-bearing contract: with the layer disabled OR the
store empty, every solve is bitwise identical to the pre-policy
defaults; decisions are pure functions of (merged store view, problem
signature), so every process reading the same files decides the same.

``SketchContext`` is stateful — every comparison below constructs a
fresh same-seed context per call so bitwise equality is meaningful.
"""

import json
import os
import subprocess
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from libskylark_tpu import plans, policy
from libskylark_tpu.core.context import SketchContext
from libskylark_tpu.linalg.least_squares import (
    approximate_least_squares,
    streaming_least_squares,
)
from libskylark_tpu.policy.decide import LS_ROUTES, ProblemSignature, choose_route
from libskylark_tpu.policy.profile import ProfileStore, load_entries
from libskylark_tpu.resilient import FaultPlan

pytestmark = pytest.mark.policy


@pytest.fixture
def policy_env(tmp_path, monkeypatch):
    """Clean policy world: enabled, guarded, fresh store dir, and no
    leakage of SKYLARK_POLICY* knobs between tests."""
    monkeypatch.setenv("SKYLARK_POLICY", "1")
    monkeypatch.setenv("SKYLARK_GUARD", "1")
    monkeypatch.setenv("SKYLARK_POLICY_MIN_SAMPLES", "3")
    monkeypatch.delenv("SKYLARK_POLICY_DIR", raising=False)
    monkeypatch.delenv("SKYLARK_POLICY_BF16", raising=False)
    store = str(tmp_path / "policy-store")
    policy.configure(store)
    policy.reset()
    policy.invalidate_cache()
    plans.clear()
    plans.reset_stats()
    yield store
    policy.configure(None)
    policy.reset()
    policy.invalidate_cache()


def _ls_problem(seed=5, m=240, n=8, dtype=np.float32):
    rng = np.random.default_rng(seed)
    A = rng.normal(size=(m, n)).astype(dtype)
    x_true = rng.normal(size=n).astype(dtype)
    b = (A @ x_true + 1e-3 * rng.normal(size=m)).astype(dtype)
    return jnp.asarray(A), jnp.asarray(b)


def _mature(A, b, runs=3, seed=7):
    """Run enough guarded solves to push the entry past min_samples.
    Every solve flushes through run_summary, so the store is on disk
    (and the merged-view cache invalidated) after each call."""
    for _ in range(runs):
        approximate_least_squares(A, b, SketchContext(seed=seed))


# ---------------------------------------------------------------------------
# bit-parity: empty store / disabled layer == historical defaults


def test_empty_store_is_bitwise_default(policy_env, monkeypatch):
    A, b = _ls_problem()
    monkeypatch.setenv("SKYLARK_POLICY", "0")
    x_off = np.asarray(approximate_least_squares(A, b, SketchContext(seed=7)))
    monkeypatch.setenv("SKYLARK_POLICY", "1")
    x_on, info = approximate_least_squares(
        A, b, SketchContext(seed=7), return_info=True
    )
    assert np.array_equal(x_off, np.asarray(x_on))
    assert info["policy"]["source"] == "default"
    assert info["policy"]["route"] == "sketch"


def test_empty_store_streaming_bit_parity(policy_env, monkeypatch):
    rng = np.random.default_rng(3)
    n, d, br = 512, 16, 128
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = rng.standard_normal(n).astype(np.float32)

    def batches(start):
        for i in range(start, n // br):
            yield X[i * br : (i + 1) * br], y[i * br : (i + 1) * br]

    monkeypatch.setenv("SKYLARK_POLICY", "0")
    x_off, _ = streaming_least_squares(batches, n, d, SketchContext(seed=9))
    monkeypatch.setenv("SKYLARK_POLICY", "1")
    x_on, info = streaming_least_squares(batches, n, d, SketchContext(seed=9))
    assert np.array_equal(np.asarray(x_off), np.asarray(x_on))
    assert info["policy"]["source"] == "default"


def test_immature_entry_stays_default(policy_env):
    """Below min_samples the profile must not influence decisions."""
    A, b = _ls_problem()
    _mature(A, b, runs=2)
    _, info = approximate_least_squares(
        A, b, SketchContext(seed=7), return_info=True
    )
    assert info["policy"]["source"] == "default"


# ---------------------------------------------------------------------------
# determinism: pure function of (store view, signature)


def test_decision_is_deterministic_across_processes(policy_env):
    A, b = _ls_problem()
    _mature(A, b, runs=4)
    view = load_entries(policy_env)
    sig = ProblemSignature(kind="ls", m=240, n=8, dtype="float32")
    here = choose_route(sig, store_view=view).to_dict()
    assert here["source"] == "profile"
    child = (
        "import json\n"
        "from libskylark_tpu.policy.decide import ProblemSignature, "
        "choose_route\n"
        "from libskylark_tpu.policy.profile import load_entries\n"
        f"view = load_entries({policy_env!r})\n"
        "sig = ProblemSignature(kind='ls', m=240, n=8, dtype='float32')\n"
        "print(json.dumps(choose_route(sig, store_view=view).to_dict()))\n"
    )
    env = dict(os.environ, SKYLARK_POLICY="1", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", child], capture_output=True, text=True,
        env=env, timeout=90, check=True,
    )
    there = json.loads(out.stdout.strip().splitlines()[-1])
    assert there == here


def test_decision_repeatable_on_same_view(policy_env):
    A, b = _ls_problem()
    _mature(A, b, runs=4)
    sig = ProblemSignature(kind="ls", m=240, n=8, dtype="float32")
    d1 = choose_route(sig, store_view=load_entries(policy_env)).to_dict()
    policy.invalidate_cache()
    d2 = choose_route(sig, store_view=load_entries(policy_env)).to_dict()
    assert d1 == d2


# ---------------------------------------------------------------------------
# what a matured profile may change


def test_matured_profile_shrinks_sketch_dim(policy_env):
    A, b = _ls_problem()
    _mature(A, b, runs=4)
    x, info = approximate_least_squares(
        A, b, SketchContext(seed=7), return_info=True
    )
    dec = info["policy"]
    assert dec["source"] == "profile"
    assert dec["sketch_size"] < min(4 * 8, 240)  # shrunk below default
    assert dec["sketch_size"] >= min(2 * 8, 240)  # never below the floor
    # the shrunk sketch still certifies on attempt 0
    assert info["recovery"]["attempts"][0]["verdict"] == "OK"
    assert np.all(np.isfinite(np.asarray(x)))


def test_explicit_overrides_beat_profile(policy_env):
    A, b = _ls_problem()
    _mature(A, b, runs=4)
    from libskylark_tpu.linalg.least_squares import LeastSquaresParams

    _, info = approximate_least_squares(
        A, b, SketchContext(seed=7),
        LeastSquaresParams(sketch_type="JLT", sketch_size=32),
        route="sketch", return_info=True,
    )
    dec = info["policy"]
    assert dec["sketch_type"] == "JLT"
    assert dec["sketch_size"] == 32
    assert dec["route"] == "sketch"


def test_unknown_route_rejected(policy_env):
    A, b = _ls_problem()
    with pytest.raises(ValueError, match="route"):
        approximate_least_squares(
            A, b, SketchContext(seed=7), route="warp-drive"
        )
    assert "warp-drive" not in LS_ROUTES


def test_bf16_first_escalates_to_f32_on_bad_certificate(
    policy_env, monkeypatch
):
    """bf16-first with a poisoned attempt 0: the certificate is not OK,
    so the call escalates back to the full-precision rerun and the store
    records the bf16 failure (which retires bf16-first for the key)."""
    A, b = _ls_problem()
    _mature(A, b, runs=3)
    monkeypatch.setenv("SKYLARK_POLICY_BF16", "1")
    x, info = approximate_least_squares(
        A, b, SketchContext(seed=7),
        fault_plan=FaultPlan(nan_at=0), return_info=True,
    )
    dec = info["policy"]
    assert dec["compute_dtype"] == "bfloat16"
    assert dec["escalated"] is True
    assert np.asarray(x).dtype == np.float32
    assert np.all(np.isfinite(np.asarray(x)))
    # the recorded failure retires bf16-first on the next decision
    policy.invalidate_cache()
    entry = load_entries(policy_env)["entries"][dec["key"]]
    assert entry["bf16"]["fail"] >= 1
    sig = ProblemSignature(kind="ls", m=240, n=8, dtype="float32")
    nxt = choose_route(sig, store_view=load_entries(policy_env))
    assert nxt.compute_dtype is None


def test_bf16_clean_run_stays_bf16_and_matches_dtype(
    policy_env, monkeypatch
):
    A, b = _ls_problem()
    _mature(A, b, runs=3)
    monkeypatch.setenv("SKYLARK_POLICY_BF16", "1")
    x, info = approximate_least_squares(
        A, b, SketchContext(seed=7), return_info=True
    )
    assert info["policy"]["compute_dtype"] == "bfloat16"
    assert "escalated" not in info["policy"]
    assert np.asarray(x).dtype == np.float32  # cast back before the solve


def _tpu_entry_with_clean_bf16_history(**extra):
    """A mature, healthy f32 key on backend "tpu" with 100 clean bf16
    runs and no failure: the history the ladder's last rung is read
    from."""
    entry = {
        "runs": 100, "updated": 100.0,
        "guard": {"ok": 100, "resketch": 0, "fallback": 0},
        "cond": {"last": 3.0, "max": 3.0},
        "sketch": {"type": "FJLT", "min_ok": 32, "default": 32},
        "bf16": {"ok": 100, "fail": 0},
        "routes": {"sketch": 100},
        "escalations": 0,
    }
    entry.update(extra)
    return entry


def test_precision_ladder_stops_at_bf16(policy_env):
    """f32 → bf16 and no further: however long and clean the bf16
    history at a key on a TPU, the decision stays on the bf16 rung."""
    sig = ProblemSignature(
        kind="ls", m=240, n=8, dtype="float32", backend="tpu"
    )
    view = {"entries": {sig.key: _tpu_entry_with_clean_bf16_history()}}
    d = choose_route(sig, store_view=view)
    assert d.source == "profile"
    assert d.compute_dtype == "bfloat16"


def test_profile_with_a_retired_rungs_record_still_loads(policy_env):
    """A store file from a build that still had a rung below bf16
    carries an ``"fp8"`` record in its entries.  It is outside input:
    the file loads, merges, folds and saves, and the record moves no
    decision."""
    sig = ProblemSignature(
        kind="ls", m=240, n=8, dtype="float32", backend="tpu"
    )
    old = _tpu_entry_with_clean_bf16_history(fp8={"ok": 3, "fail": 1})
    payload = {"entries": {sig.key: old}, "plans": [], "meta": {}}
    crc = zlib.crc32(
        json.dumps(payload, sort_keys=True, separators=(",", ":")).encode()
    ) & 0xFFFFFFFF
    os.makedirs(policy_env)
    with open(os.path.join(policy_env, "profile-4242.json"), "w") as fh:
        json.dump({"version": 1, "pid": 4242, "payload": payload,
                   "crc": crc}, fh, sort_keys=True)
    policy.invalidate_cache()
    view = load_entries(policy_env)
    assert view["corrupt_files"] == 0
    assert view["entries"][sig.key]["bf16"] == {"ok": 100, "fail": 0}
    with_record = choose_route(sig, store_view=view)
    without = choose_route(
        sig,
        store_view={
            "entries": {sig.key: _tpu_entry_with_clean_bf16_history()}
        },
    )
    assert with_record.to_dict() == without.to_dict()
    assert with_record.compute_dtype == "bfloat16"
    # a new observation folds onto the merged entry and the file this
    # process writes is read back whole
    store = ProfileStore(policy_env)
    store.fold(sig.key, {"ok0": True, "route": "sketch", "bf16": "ok"},
               now=200.0)
    assert store.save(now=200.0) is not None
    entry = load_entries(policy_env)["entries"][sig.key]
    assert load_entries(policy_env)["corrupt_files"] == 0
    assert entry["runs"] == 101
    assert entry["bf16"] == {"ok": 101, "fail": 0}
    after = choose_route(sig, store_view=load_entries(policy_env))
    assert after.compute_dtype == "bfloat16"


# ---------------------------------------------------------------------------
# store: merge, corruption, persistence


def test_corrupt_store_files_are_skipped_not_trusted(policy_env):
    store = ProfileStore(policy_env)
    store.fold("ls|cpu|float32|r8c3", {"ok0": True, "route": "sketch"},
               now=100.0)
    assert store.save(now=100.0) is not None
    # torn write: plain garbage
    with open(os.path.join(policy_env, "profile-9001.json"), "w") as fh:
        fh.write('{"version": 1, "payl')
    # byte flip: valid JSON, wrong CRC
    with open(os.path.join(policy_env, "profile-9002.json"), "w") as fh:
        json.dump({"version": 1, "pid": 9002,
                   "payload": {"entries": {"x": {"runs": 99}}},
                   "crc": 12345}, fh)
    policy.invalidate_cache()
    view = load_entries(policy_env)
    assert view["corrupt_files"] == 2
    assert set(view["entries"]) == {"ls|cpu|float32|r8c3"}
    assert view["entries"]["ls|cpu|float32|r8c3"]["runs"] == 1


def test_merge_is_last_writer_wins_per_key(policy_env):
    # Two "processes" write the same key; both files end up on disk
    # (saves are renamed aside, since both stores share this test's pid)
    # and the reader must pick the newer entry.
    a = ProfileStore(policy_env)
    a.fold("k", {"ok0": True, "route": "sketch"}, now=100.0)
    os.replace(a.save(now=100.0),
               os.path.join(policy_env, "profile-1111.json"))
    policy.invalidate_cache()
    b = ProfileStore(policy_env)
    b.fold("k", {"ok0": True, "route": "sketch"}, now=200.0)
    os.replace(b.save(now=200.0),
               os.path.join(policy_env, "profile-2222.json"))
    policy.invalidate_cache()
    view = load_entries(policy_env)
    # the newer file's entry (updated=200) wins; it seeded from the
    # merged view, so the run count carried forward to 2
    assert view["entries"]["k"]["updated"] == 200.0
    assert view["entries"]["k"]["runs"] == 2


def test_observations_persist_and_fold(policy_env):
    A, b = _ls_problem()
    _mature(A, b, runs=3)
    view = load_entries(policy_env)
    key = ProblemSignature(kind="ls", m=240, n=8, dtype="float32").key
    entry = view["entries"][key]
    assert entry["runs"] == 3
    assert entry["guard"]["ok"] == 3
    assert entry["guard"]["fallback"] == 0
    assert entry["sketch"]["default"] == 32
    assert entry["routes"] == {"sketch": 3}
    assert entry["cond"]["max"] is not None


# ---------------------------------------------------------------------------
# warm start


def test_warm_start_replays_plans_bitwise(policy_env):
    A, b = _ls_problem()
    x0 = np.asarray(approximate_least_squares(A, b, SketchContext(seed=7)))
    view = load_entries(policy_env)
    assert view["plans"], "solve should have recorded hot plan keys"

    prev_cache = jax.config.jax_compilation_cache_dir
    try:
        # a "new process": empty plan cache, fresh merged view
        plans.clear()
        plans.reset_stats()
        policy.invalidate_cache()
        ws = policy.warm_start(policy_env)
        assert ws["enabled"] is True
        assert ws["plans_replayed"] >= 1
        assert ws["plans_skipped"] == 0
        assert plans.stats()["traces"] >= 1
        st0 = plans.stats()
        x1 = np.asarray(
            approximate_least_squares(A, b, SketchContext(seed=7))
        )
        assert np.array_equal(x0, x1)  # replay never changes results
        assert plans.stats()["hits"] > st0["hits"]  # and the replay hit
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_cache)


def test_warm_start_disabled_or_storeless_is_noop(policy_env, monkeypatch):
    assert policy.warm_start(str(policy_env) + "-missing")["enabled"] is False
    monkeypatch.setenv("SKYLARK_POLICY", "0")
    assert policy.warm_start(policy_env)["enabled"] is False


# ---------------------------------------------------------------------------
# disabled layer: no reads, no writes


def test_disabled_layer_writes_nothing(policy_env, monkeypatch):
    monkeypatch.setenv("SKYLARK_POLICY", "0")
    A, b = _ls_problem()
    approximate_least_squares(A, b, SketchContext(seed=7))
    assert not os.path.isdir(policy_env) or not os.listdir(policy_env)
