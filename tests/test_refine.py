"""Certified mixed-precision refinement (ISSUE PR 14): the refine
route, its guard/ladder integration, the policy earning/retirement
contract, the served cond-est endpoint, and the quasirandom sketch's
interchange.

Load-bearing pins:

- route-OFF bitwise parity — exercising the refine machinery must not
  perturb the default sketch route by a single bit;
- certified convergence — the gate only passes on a freshly recomputed
  residual and the answer matches the exact solve;
- stagnation falls down the EXISTING ladder (resketch → grow → exact
  dense) under guarding, raises code 115 without it;
- the policy earns the refine route only from recorded certified refine
  history and a single stagnation retires it;
- served cond-est results are identical solo vs coalesced;
- the QJLT sketch round-trips through the JSON interchange bitwise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import enable_x64

from libskylark_tpu import plans, policy, serve
from libskylark_tpu.core.context import SketchContext
from libskylark_tpu.linalg.least_squares import approximate_least_squares
from libskylark_tpu.policy.decide import (
    LS_ROUTES,
    ProblemSignature,
    choose_route,
)
from libskylark_tpu.policy.profile import load_entries
from libskylark_tpu.resilient import FaultPlan
from libskylark_tpu.solvers.refine import RefineParams, refine_least_squares
from libskylark_tpu.utils import exceptions as ex

pytestmark = pytest.mark.refine


def _ls_problem(seed=5, m=400, n=16, dtype=np.float64, noise=1e-3):
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((m, n)).astype(dtype)
    x_true = rng.standard_normal(n).astype(dtype)
    b = (A @ x_true + noise * rng.standard_normal(m)).astype(dtype)
    return jnp.asarray(A), jnp.asarray(b)


# ---------------------------------------------------------------------------
# route-OFF bitwise parity


def test_route_off_bitwise_parity():
    """The sketch route must be bit-identical before and after the
    refine machinery runs: refine draws from its own context, so the
    default route's sketch stream (and the plan cache it warms) is
    untouched."""
    A, b = _ls_problem(dtype=np.float32)
    x_before = np.asarray(
        approximate_least_squares(A, b, SketchContext(seed=7))
    )
    X, info = refine_least_squares(A, b, SketchContext(seed=31))
    assert info["refine"]["converged"]
    x_after = np.asarray(
        approximate_least_squares(A, b, SketchContext(seed=7))
    )
    assert np.array_equal(x_before, x_after)


def test_refine_is_an_explicit_route():
    assert "refine" in LS_ROUTES
    A, b = _ls_problem(dtype=np.float32)
    x, info = approximate_least_squares(
        A, b, SketchContext(seed=7), route="refine", return_info=True
    )
    assert info["policy"]["route"] == "refine"
    assert info["refine"]["converged"]
    assert np.all(np.isfinite(np.asarray(x)))


# ---------------------------------------------------------------------------
# certified convergence


def test_certified_convergence_f64():
    """f64 inputs refine to the exact solve's accuracy through an f32
    factorization: the gate only passes on a freshly recomputed
    residual, so convergence is certified, not assumed."""
    with enable_x64():
        A, b = _ls_problem()
        X, info = refine_least_squares(A, b, SketchContext(seed=11))
        rf = info["refine"]
        assert rf["converged"] and rf["halt"] == "converged"
        assert rf["rung"] == "f32"  # f64 never silently demotes to bf16
        assert rf["iters"] >= 1
        assert rf["gradient_norm"] <= rf["gate"]
        xs = np.linalg.lstsq(np.asarray(A), np.asarray(b), rcond=None)[0]
        r_exact = np.linalg.norm(np.asarray(A) @ xs - np.asarray(b))
        r_ref = float(jnp.linalg.norm(A @ X - b))
        assert r_ref <= r_exact * (1 + 1e-9)


def test_f32_inputs_ride_bf16_rung():
    A, b = _ls_problem(dtype=np.float32)
    X, info = refine_least_squares(A, b, SketchContext(seed=11))
    assert info["refine"]["rung"] == "bf16+f32"
    assert info["refine"]["converged"]
    xs = np.linalg.lstsq(np.asarray(A), np.asarray(b), rcond=None)[0]
    r_exact = np.linalg.norm(np.asarray(A) @ xs - np.asarray(b))
    r_ref = float(jnp.linalg.norm(A @ X - b))
    assert r_ref <= r_exact * (1 + 1e-4)


def test_sketch_cannot_shrink_reports_exact():
    """s >= m: the honest answer is the exact solve, reported as such."""
    A, b = _ls_problem(m=24, n=16)
    X, info = refine_least_squares(A, b, SketchContext(seed=3))
    assert info["refine"]["rung"] == "exact-f64"
    assert info["refine"]["iters"] == 0


# ---------------------------------------------------------------------------
# stagnation: ladder under guarding, code 115 without


def test_stagnation_falls_down_ladder_to_exact():
    """A refinement that cannot meet its gate (one sweep, impossible
    rtol) demotes every attempt to RESKETCH; the EXISTING ladder walks
    fresh-seed → grow → exact dense, and the caller still gets the
    right answer with the fallback on the record."""
    A, b = _ls_problem(dtype=np.float32)
    X, info = refine_least_squares(
        A, b, SketchContext(seed=7),
        RefineParams(max_iters=1, rtol=1e-300),
    )
    rf = info["refine"]
    assert rf["halt"] == "fallback" and rf["rung"] == "exact-f64"
    rec = info["recovery"]
    assert rec["guarded"]
    assert any(a["verdict"] == "RESKETCH" for a in rec["attempts"])
    xs = np.linalg.lstsq(np.asarray(A), np.asarray(b), rcond=None)[0]
    np.testing.assert_allclose(np.asarray(X), xs, rtol=1e-3, atol=1e-4)


def test_transient_corruption_recovers_via_resketch():
    """A one-shot corrupted sketch (FaultPlan attempt-0 NaN) certifies
    RESKETCH and attempt 1 converges on a fresh seed."""
    A, b = _ls_problem(dtype=np.float32)
    X, info = refine_least_squares(
        A, b, SketchContext(seed=7), fault_plan=FaultPlan(nan_at=0)
    )
    rec = info["recovery"]
    assert rec["attempts"][0]["verdict"] == "RESKETCH"
    assert info["refine"]["converged"]
    assert np.all(np.isfinite(np.asarray(X)))


def test_guard_off_stagnation_raises_115(monkeypatch):
    monkeypatch.setenv("SKYLARK_GUARD", "0")
    A, b = _ls_problem(dtype=np.float32)
    with pytest.raises(ex.RefinementError) as e:
        refine_least_squares(
            A, b, SketchContext(seed=7),
            RefineParams(max_iters=1, rtol=1e-300),
        )
    assert e.value.code == 115
    assert ex.RefinementError.code == 115


# ---------------------------------------------------------------------------
# policy: earned from history, retired on stagnation


@pytest.fixture
def policy_env(tmp_path, monkeypatch):
    monkeypatch.setenv("SKYLARK_POLICY", "1")
    monkeypatch.setenv("SKYLARK_GUARD", "1")
    monkeypatch.setenv("SKYLARK_POLICY_MIN_SAMPLES", "3")
    monkeypatch.delenv("SKYLARK_POLICY_DIR", raising=False)
    store = str(tmp_path / "policy-store")
    policy.configure(store)
    policy.reset()
    policy.invalidate_cache()
    plans.clear()
    yield store
    policy.configure(None)
    policy.reset()
    policy.invalidate_cache()


def test_refine_route_earned_from_certified_history(policy_env):
    A, b = _ls_problem(dtype=np.float32, m=240, n=8)
    for _ in range(4):
        _, info = approximate_least_squares(
            A, b, SketchContext(seed=7), route="refine", return_info=True
        )
        assert info["refine"]["converged"]
    sig = ProblemSignature(kind="ls", m=240, n=8, dtype="float32")
    d = choose_route(sig, store_view=load_entries(policy_env))
    assert d.route == "refine" and d.source == "profile"
    assert any("refine earned" in r for r in d.reasons)


def test_refine_never_earned_without_history(policy_env):
    """A matured entry with NO recorded refine runs keeps the sketch
    route — history is the only way in."""
    A, b = _ls_problem(dtype=np.float32, m=240, n=8)
    for _ in range(4):
        approximate_least_squares(A, b, SketchContext(seed=7))
    sig = ProblemSignature(kind="ls", m=240, n=8, dtype="float32")
    d = choose_route(sig, store_view=load_entries(policy_env))
    assert d.source == "profile" and d.route == "sketch"


def test_single_stagnation_retires_refine(policy_env):
    """choose_route on a crafted view: certified history earns the
    route; one recorded stagnation (or a guard blemish) retires it."""
    sig = ProblemSignature(kind="ls", m=240, n=8, dtype="float32")
    entry = {
        "runs": 5,
        "guard": {"fallback": 0, "resketch": 0},
        "cond": {"max": 3.0},
        "refine": {"ok": 4, "stagnate": 0, "iters": 20, "rung": "bf16+f32"},
    }
    view = {"entries": {sig.key: dict(entry)}}
    assert choose_route(sig, store_view=view).route == "refine"
    retired = dict(entry, refine=dict(entry["refine"], stagnate=1))
    view = {"entries": {sig.key: retired}}
    assert choose_route(sig, store_view=view).route == "sketch"
    unhealthy = dict(entry, guard={"fallback": 0, "resketch": 2})
    view = {"entries": {sig.key: unhealthy}}
    assert choose_route(sig, store_view=view).route != "refine"


# ---------------------------------------------------------------------------
# served cond-est


_SRV_RNG = np.random.default_rng(1234)
_SRV_A = _SRV_RNG.standard_normal((64, 5))


def _cond_server(max_coalesce, seed=42):
    srv = serve.Server(
        serve.ServeParams(
            max_coalesce=max_coalesce, warm_start=False, prime=False
        ),
        seed=seed,
    )
    srv.registry.register_system(
        "sys", _SRV_A, context=SketchContext(seed=9)
    )
    return srv


def test_served_cond_est_coalesced_equals_solo():
    solo_srv = _cond_server(1)
    solo_srv.start()
    solo = solo_srv.call({"op": "cond_est", "system": "sys"})
    solo_srv.stop()
    assert solo["ok"], solo
    rep = solo["result"]
    assert rep["system"] == "sys" and rep["n"] == 5
    assert rep["effective_rank"] == 5
    assert np.isfinite(rep["cond"]) and rep["cond"] >= 1.0
    assert rep["sigma_max"] >= rep["sigma_min"] > 0

    co_srv = _cond_server(8)
    futures = [
        co_srv.submit({"op": "cond_est", "system": "sys"}) for _ in range(6)
    ]
    co_srv.start()
    results = [f.result() for f in futures]
    co_srv.stop()
    for r in results:
        assert r["ok"]
        assert r["result"] == rep  # identical dict, coalesced or solo


def test_served_cond_est_unknown_system():
    srv = _cond_server(1)
    srv.start()
    r = srv.call({"op": "cond_est", "system": "nope"})
    srv.stop()
    assert not r["ok"]
    assert r["error"]["code"] == ex.InvalidParameters("x").code


# ---------------------------------------------------------------------------
# quasirandom sketch interchange


def test_qjlt_json_interchange_bitwise():
    from libskylark_tpu.sketch.base import create_sketch, from_json

    m, s = 256, 64
    A = jnp.asarray(
        np.random.default_rng(2).standard_normal((m, 12)).astype(np.float32)
    )
    S = create_sketch("QJLT", m, s, SketchContext(seed=17))
    SA = plans.apply(S, A, "columnwise")
    S2 = from_json(S.to_json())
    SA2 = plans.apply(S2, A, "columnwise")
    assert np.array_equal(np.asarray(SA), np.asarray(SA2))
    d = S.to_dict()
    assert d["leap"] == S.leap and d["skip"] == S.skip


def test_refine_rides_qjlt_sketch():
    A, b = _ls_problem(dtype=np.float32)
    X, info = refine_least_squares(
        A, b, SketchContext(seed=13), RefineParams(sketch_type="QJLT")
    )
    assert info["refine"]["converged"]
    xs = np.linalg.lstsq(np.asarray(A), np.asarray(b), rcond=None)[0]
    r_exact = np.linalg.norm(np.asarray(A) @ xs - np.asarray(b))
    r_ref = float(jnp.linalg.norm(A @ X - b))
    assert r_ref <= r_exact * (1 + 1e-4)
