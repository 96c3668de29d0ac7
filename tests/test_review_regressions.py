"""Regression tests for review findings: sparse SVD path, rank validation,
1-D hash-sketch apply."""

import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import sparse as jsparse

from libskylark_tpu import SketchContext
from libskylark_tpu.linalg import SVDParams, approximate_svd
from libskylark_tpu.sketch import CWT


@pytest.mark.slow
def test_approximate_svd_on_bcoo(rng):
    dense = rng.standard_normal((60, 20))
    dense[rng.random((60, 20)) < 0.6] = 0.0
    A = jsparse.BCOO.fromdense(jnp.asarray(dense))
    U, s, V = approximate_svd(A, 5, SketchContext(seed=11), SVDParams(num_iterations=1))
    s_true = np.linalg.svd(dense, compute_uv=False)
    np.testing.assert_allclose(np.asarray(s)[:2], s_true[:2], rtol=0.1)


def test_rank_too_large_raises(rng):
    A = jnp.asarray(rng.standard_normal((30, 10)))
    with pytest.raises(ValueError, match="rank"):
        approximate_svd(A, 50, SketchContext(seed=1))


def test_hash_sketch_1d_vector(rng):
    n, s = 40, 12
    v = jnp.asarray(rng.standard_normal(n))
    S = CWT(n, s, SketchContext(seed=3))
    out_vec = S.apply(v, "columnwise")
    out_mat = S.apply(v[:, None], "columnwise")
    assert out_vec.shape == (s,)
    np.testing.assert_allclose(np.asarray(out_vec), np.asarray(out_mat[:, 0]))
    out_r = S.apply(v, "rowwise")
    out_r_mat = S.apply(v[None, :], "rowwise")
    assert out_r.shape == (s,)
    np.testing.assert_allclose(np.asarray(out_r), np.asarray(out_r_mat[0]))


def test_cli_sparse_path(tmp_path, rng):
    from libskylark_tpu.cli.svd import main
    from libskylark_tpu.io import write_libsvm

    X = rng.standard_normal((30, 10))
    X[rng.random((30, 10)) < 0.5] = 0.0
    write_libsvm(tmp_path / "d", X, np.ones(30))
    rc = main(
        [str(tmp_path / "d"), "--sparse", "--rank", "3", "--prefix", str(tmp_path / "o")]
    )
    assert rc == 0
    assert np.load(tmp_path / "o.S.npy").shape == (3,)


def test_hoisted_operand_cache_skipped_both_ways_under_trace():
    """``hoistable_operands`` memoizes per sketch — except mid-trace,
    where an operand built from tracers must not be cached and a cached
    concrete operand must not be returned (it would be baked into the
    caller's executable as a constant)."""
    import jax

    from libskylark_tpu import SketchContext
    from libskylark_tpu.sketch import CWT, JLT

    for S in (JLT(64, 16, SketchContext(seed=1)),
              CWT(64, 16, SketchContext(seed=2))):
        seen = {}

        @jax.jit
        def traced(v):
            ops = S.hoistable_operands(jnp.float32)
            seen["traced"] = any(
                isinstance(leaf, jax.core.Tracer)
                for leaf in jax.tree.leaves(ops)
            )
            return v * 2

        traced(jnp.ones(4))
        assert seen["traced"] and "_hoist_cache" not in S.__dict__
        eager = S.hoistable_operands(jnp.float32)
        assert S.hoistable_operands(jnp.float32) is eager  # memoized
        traced.clear_cache()
        traced(jnp.ones(4))
        assert seen["traced"]  # the cached concrete operand stayed out


def test_halton_window_tiered_digits_bit_identical():
    """window()'s per-base digit tiers must be BIT-identical to the full
    41-digit loop (skipped iterations add exactly 0.0)."""
    from libskylark_tpu.core.quasirand import (
        LeapedHaltonSequence,
        primes,
        radical_inverse,
    )

    seq = LeapedHaltonSequence(200)
    for idx0, num in ((0, 16), (1000, 8), (123456, 4)):
        out = seq.window(idx0, num, dtype=jnp.float64)
        itype = jnp.int64
        idx = (idx0 + jnp.arange(num, dtype=itype))[:, None] * seq.leap
        p = jnp.asarray(primes(seq.d))[None, :].astype(itype)
        full = radical_inverse(p, idx, ndigits=41).astype(jnp.float64)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(full))


def test_halton_window_exact_at_power_boundaries():
    """Digit counts must be exact integers: float logs undercount at
    p^k boundaries (review r5), dropping the leading digit for those
    columns.  Constructs a window whose max index sits exactly at a
    prime power and checks against the full 41-digit loop."""
    from libskylark_tpu.core.quasirand import (
        LeapedHaltonSequence,
        primes,
        radical_inverse,
    )

    seq = LeapedHaltonSequence(30, leap=1)  # leap=1: indices are raw
    p5 = int(primes(30)[2])  # base 5
    idx0 = p5**6 - 3  # window straddles 5^6 exactly
    out = seq.window(idx0, 6, dtype=jnp.float64)
    idx = (idx0 + jnp.arange(6, dtype=jnp.int64))[:, None]
    p = jnp.asarray(primes(seq.d))[None, :].astype(jnp.int64)
    full = radical_inverse(p, idx, ndigits=41).astype(jnp.float64)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(full))


def test_halton_window_zero_dims():
    from libskylark_tpu.core.quasirand import LeapedHaltonSequence

    out = LeapedHaltonSequence(0, leap=7).window(0, 4)
    assert out.shape == (4, 0)


@pytest.mark.guard
def test_solver_entrypoints_document_and_populate_recovery():
    """Static contract check (ISSUE PR 4): every public solver entrypoint
    that returns ``(x, info)`` must document ``info["recovery"]`` in its
    docstring AND populate it in source, so the guard ledger can never be
    silently dropped from one solver's info dict."""
    import inspect

    from libskylark_tpu.linalg.least_squares import (
        approximate_least_squares,
        streaming_least_squares,
    )
    from libskylark_tpu.ml.krr import (
        approximate_kernel_ridge,
        streaming_approximate_kernel_ridge,
    )
    from libskylark_tpu.solvers.accelerated import (
        faster_least_squares,
        lsrn_least_squares,
    )
    from libskylark_tpu.streaming.drivers import sketch_least_squares

    entrypoints = [
        approximate_least_squares,
        streaming_least_squares,
        faster_least_squares,
        lsrn_least_squares,
        sketch_least_squares,
        approximate_kernel_ridge,  # ledger rides on model.info
        streaming_approximate_kernel_ridge,
    ]
    for fn in entrypoints:
        doc = inspect.getdoc(fn) or ""
        assert '"recovery"' in doc or "recovery" in doc, (
            f"{fn.__module__}.{fn.__name__} returns an info dict but its "
            f'docstring does not document info["recovery"]'
        )
        src = inspect.getsource(fn)
        assert '"recovery"' in src or "report.to_dict()" in src or (
            # thin wrappers may delegate the ledger to the layer below —
            # but then the delegate must populate it
            "sketch_least_squares" in src
        ), (
            f"{fn.__module__}.{fn.__name__} does not populate "
            f'info["recovery"] (or delegate to a layer that does)'
        )


@pytest.mark.telemetry
def test_solver_entrypoints_emit_run_summary():
    """Static contract check (ISSUE PR 5): every public solver entrypoint
    that returns ``(x, info)`` must emit a terminal
    ``telemetry.run_summary`` event carrying its ``info`` dict — or
    delegate to the layer that does — so an enabled ledger always closes
    with the counters-vs-info record the acceptance check reads."""
    import inspect

    from libskylark_tpu.linalg.least_squares import (
        approximate_least_squares,
        streaming_least_squares,
    )
    from libskylark_tpu.ml.krr import (
        approximate_kernel_ridge,
        streaming_approximate_kernel_ridge,
    )
    from libskylark_tpu.solvers.accelerated import (
        faster_least_squares,
        lsrn_least_squares,
    )
    from libskylark_tpu.streaming.drivers import sketch_least_squares

    entrypoints = [
        approximate_least_squares,
        streaming_least_squares,
        faster_least_squares,
        lsrn_least_squares,
        sketch_least_squares,
        approximate_kernel_ridge,
        streaming_approximate_kernel_ridge,
    ]
    for fn in entrypoints:
        src = inspect.getsource(fn)
        assert "telemetry.run_summary(" in src or (
            # thin wrappers may delegate the terminal event to the
            # streaming driver below — which emits it itself
            "sketch_least_squares" in src or "kernel_ridge(" in src
        ), (
            f"{fn.__module__}.{fn.__name__} returns (x, info) but never "
            "emits a terminal telemetry.run_summary (or delegates to a "
            "layer that does)"
        )


@pytest.mark.telemetry
def test_disabled_telemetry_registers_no_atexit_hooks():
    """With ``SKYLARK_TELEMETRY`` unset/0, importing the library and
    emitting disabled-path events must leave the process's atexit table
    untouched (the ledger registers its flush hook only when a file
    actually opens).  Measured AFTER the library import in a fresh
    subprocess: jax itself registers atexit hooks at import time, so the
    contract is 'telemetry adds zero', not 'the table is empty'."""
    import subprocess
    import sys

    code = (
        "import os\n"
        "os.environ['SKYLARK_TELEMETRY'] = '0'\n"
        "os.environ.pop('SKYLARK_TELEMETRY_DIR', None)\n"
        "import atexit\n"
        "import libskylark_tpu\n"
        "from libskylark_tpu import telemetry\n"
        "base = atexit._ncallbacks()\n"
        "telemetry.emit('probe', 'noop', k=1)\n"
        "telemetry.inc('noop.counter')\n"
        "with telemetry.span('noop.span'):\n"
        "    pass\n"
        "assert telemetry.ledger_path() is None, telemetry.ledger_path()\n"
        "assert atexit._ncallbacks() == base, (base, atexit._ncallbacks())\n"
        "print('ZERO-ATEXIT-OK')\n"
    )
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env=env,
        timeout=110,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ZERO-ATEXIT-OK" in out.stdout


@pytest.mark.policy
def test_snapshot_folds_policy_counter_group():
    """Static contract check (ISSUE PR 9): ``telemetry.snapshot()`` must
    fold the ``policy.*`` counters into a ``"policy"`` group, and the
    terminal ``run_summary`` must flush the policy store BEFORE its own
    enabled gate — profiles persist even with telemetry off."""
    import importlib
    import inspect

    # the telemetry package exports a report() *function*; reach the
    # module itself through importlib
    report = importlib.import_module("libskylark_tpu.telemetry.report")

    snap_src = inspect.getsource(report.snapshot)
    assert '"policy"' in snap_src and "policy." in snap_src, (
        "telemetry.snapshot() no longer folds the policy.* counter "
        'group into snap["policy"] (docs/autotuning.md contract)'
    )
    rs_src = inspect.getsource(report.run_summary)
    flush_at = rs_src.find("policy.flush")
    gate_at = rs_src.find("config.enabled()")
    assert flush_at != -1, (
        "telemetry.run_summary() no longer flushes the policy profile "
        "store (warm-start profiles would silently stop persisting)"
    )
    assert gate_at == -1 or flush_at < gate_at, (
        "policy.flush must run before run_summary's telemetry-enabled "
        "gate: profiles persist even with SKYLARK_TELEMETRY off"
    )


@pytest.mark.serve
def test_snapshot_folds_serve_counter_group():
    """Static contract check (ISSUE PR 10): ``telemetry.snapshot()`` must
    fold the ``serve.*`` counters into a ``"serve"`` group (with the
    derived coalesce ratio and latency percentiles) — the SLO surface
    docs/serving.md points operators at."""
    import importlib
    import inspect

    report = importlib.import_module("libskylark_tpu.telemetry.report")
    snap_src = inspect.getsource(report.snapshot)
    assert '"serve"' in snap_src and "serve." in snap_src, (
        "telemetry.snapshot() no longer folds the serve.* counter "
        'group into snap["serve"] (docs/serving.md contract)'
    )
    assert "coalesce_ratio" in snap_src, (
        "snapshot()['serve'] no longer derives the coalesce ratio"
    )


@pytest.mark.serve
def test_disabled_telemetry_server_is_pure_and_hookless():
    """With ``SKYLARK_TELEMETRY`` unset/0, running a full serve
    round-trip (admit -> coalesce -> execute -> respond) must add zero
    atexit hooks AND return bit-identical results to a second same-seed
    server in the same process — the telemetry fast path cannot perturb
    the serve numerics or leave process-lifetime residue."""
    import os
    import subprocess
    import sys

    code = (
        "import os\n"
        "os.environ['SKYLARK_TELEMETRY'] = '0'\n"
        "os.environ.pop('SKYLARK_TELEMETRY_DIR', None)\n"
        "import atexit\n"
        "import numpy as np\n"
        "import libskylark_tpu\n"
        "from libskylark_tpu import serve\n"
        "from libskylark_tpu.core.context import SketchContext\n"
        "rng = np.random.default_rng(0)\n"
        "A = rng.standard_normal((48, 4))\n"
        "bs = [rng.standard_normal(48) for _ in range(3)]\n"
        "def run():\n"
        "    p = serve.ServeParams(warm_start=False, prime=False)\n"
        "    srv = serve.Server(p, seed=5)\n"
        "    srv.registry.register_system('s', A,\n"
        "                                 context=SketchContext(seed=2))\n"
        "    futs = [srv.submit(serve.make_request('ls_solve', system='s',\n"
        "                                          b=b)) for b in bs]\n"
        "    srv.start()\n"
        "    out = [np.asarray(f.result()['result']) for f in futs]\n"
        "    srv.stop()\n"
        "    return out\n"
        "one = run()\n"
        "base = atexit._ncallbacks()\n"
        "two = run()\n"
        "assert atexit._ncallbacks() == base, (base, atexit._ncallbacks())\n"
        "assert all((a == b).all() for a, b in zip(one, two))\n"
        "from libskylark_tpu import telemetry\n"
        "assert telemetry.ledger_path() is None\n"
        "print('SERVE-PURE-OK')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env=env,
        timeout=110,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SERVE-PURE-OK" in out.stdout


@pytest.mark.overlap
def test_no_read_after_donation_lint():
    """Static donation lint (ISSUE PR 11): buffer donation invalidates
    the argument after the call, so every ``donate_argnums`` site in the
    library must live in an audited module, the engine must snapshot via
    ``plans.copy_for_donation`` before handing an accumulator to a
    donating executable, and its chunk-boundary sync must run BEFORE the
    sentinel read / checkpoint capture — a checkpoint must never hold a
    buffer a donating step is still allowed to alias.  A grep over call
    sites rather than a runtime probe: CPU silently ignores donation, so
    only TPU runs would catch a read-after-donate dynamically."""
    import inspect
    import pathlib

    import libskylark_tpu

    pkg = pathlib.Path(libskylark_tpu.__file__).parent
    # Every module allowed to spell donate_argnums; new sites must be
    # audited for read-after-donation and added here deliberately.
    audited = {
        pkg / "plans" / "plan.py",
        pkg / "streaming" / "drivers.py",
        # stream_feature_blocks' row-slot buffer write: each donated
        # buffer enters `write` exactly once per step and the old acc is
        # discarded; the engine's _entry_acc snapshot (gated on the same
        # donation_enabled()) covers sentinel replay, and checkpoints
        # capture post-chunk outputs, never donated inputs.
        pkg / "ml" / "distributed.py",
    }
    offenders = [
        str(p.relative_to(pkg))
        for p in sorted(pkg.rglob("*.py"))
        if p not in audited and "donate_argnums" in p.read_text()
    ]
    assert not offenders, (
        f"unaudited donate_argnums sites: {offenders}; audit each for "
        "read-after-donation (donated buffers are invalid after the "
        "call) and extend the whitelist in this test"
    )

    from libskylark_tpu.streaming import engine

    src = inspect.getsource(engine.run_stream)
    assert "copy_for_donation" in src, (
        "run_stream no longer snapshots the accumulator via "
        "plans.copy_for_donation before donating folds — a resumed "
        "checkpoint could alias a donated buffer"
    )
    sync_at = src.find("chunk_sync")
    sentinel_at = src.find("stream.sentinel_checks")
    assert sync_at != -1, (
        "run_stream lost its chunk-boundary sync (overlap contract: "
        "one block_until_ready per chunk, before state capture)"
    )
    assert sentinel_at == -1 or sync_at < sentinel_at, (
        "chunk_sync must run before the guard-sentinel read / "
        "checkpoint capture: an in-flight donated accumulator must "
        "never be observed by host-side state"
    )

    # kernel_ridge's donating update is the other audited site: its
    # donated arguments must be rebound from the call's RESULT, never
    # read again from the pre-call names.
    from libskylark_tpu.streaming import drivers

    kr = inspect.getsource(drivers.kernel_ridge)
    assert "donate_argnums" not in kr or "copy_for_donation" in kr or (
        "= update(" in kr
    ), "kernel_ridge must rebind donated accumulators from update()'s result"


def test_error_codes_documented_and_traceable(tmp_path, monkeypatch):
    """Error-code contract (ISSUE PR 12): the 100-115 ladder is only
    useful if every code (a) has a row in docs/fault_tolerance.md's
    matrix a supervisor can act on, and (b) surfaces through
    ``telemetry.error_event`` with a mandatory ``code`` attr so traces,
    the ledger, and the ``error.code.<n>`` counters all agree.  Static
    over the exception taxonomy so ADDING a code without documenting it
    fails here, not in an incident."""
    import inspect
    import pathlib

    from libskylark_tpu import telemetry
    from libskylark_tpu.utils import exceptions as ex

    classes = [
        obj
        for _, obj in inspect.getmembers(ex, inspect.isclass)
        if issubclass(obj, ex.SkylarkError)
    ]
    codes = {cls.code for cls in classes}
    assert codes == set(range(100, 119)), codes  # the ladder, no gaps

    doc = (
        pathlib.Path(__file__).parent.parent / "docs" / "fault_tolerance.md"
    ).read_text()
    undocumented = [c for c in sorted(codes) if f"| {c} |" not in doc]
    assert not undocumented, (
        f"error codes missing a docs/fault_tolerance.md matrix row: "
        f"{undocumented}"
    )

    monkeypatch.setenv("SKYLARK_TELEMETRY", "1")
    telemetry.configure(tmp_path)
    telemetry.reset()
    try:
        for cls in classes:
            tctx = telemetry.mint(f"probe-{cls.code}")
            with telemetry.activate([tctx]):
                telemetry.error_event("probe", cls("probe"))
            evs = [e for e in tctx.events if e["kind"] == "error"]
            assert evs and evs[-1]["code"] == cls.code, cls
        counters = telemetry.REGISTRY.snapshot()["counters"]
        for cls in classes:
            assert counters.get(f"error.code.{cls.code}", 0) >= 1, cls
        telemetry.flush()
        import json

        ledger = [
            json.loads(line)
            for line in open(telemetry.ledger_path(), encoding="utf-8")
        ]
        ledger_codes = {
            r["attrs"]["code"] for r in ledger if r["kind"] == "error"
        }
        assert codes <= ledger_codes
    finally:
        telemetry.close()
        telemetry.configure(None)
        telemetry.reset()


def test_env_knobs_documented():
    """Env-knob doc contract (ISSUE PR 14): every ``SKYLARK_*``
    environment variable the library reads must appear somewhere under
    ``docs/`` — a knob an operator cannot discover is a support
    incident, not a feature.  Static census: grep the package for
    environ/getenv reads (with a short window for wrapped call sites)
    and assert each harvested token has a docs mention."""
    import pathlib
    import re

    root = pathlib.Path(__file__).parent.parent
    knobs = set()
    for path in (root / "libskylark_tpu").rglob("*.py"):
        lines = path.read_text(encoding="utf-8").splitlines()
        for i, line in enumerate(lines):
            if "environ" in line or "getenv" in line:
                window = "\n".join(lines[i : i + 3])
                knobs.update(re.findall(r"SKYLARK_[A-Z0-9_]+", window))
    # The census going empty means the grep rotted, not that the
    # library grew knob-free — fail loudly either way.
    assert len(knobs) >= 20, f"env-knob census looks stale: {sorted(knobs)}"
    docs = "\n".join(
        p.read_text(encoding="utf-8")
        for p in sorted((root / "docs").glob("*.md"))
    )
    undocumented = sorted(k for k in knobs if k not in docs)
    assert not undocumented, (
        f"SKYLARK_* knobs read by the library but absent from docs/: "
        f"{undocumented}"
    )


@pytest.mark.graph
@pytest.mark.serve
def test_graph_serve_ops_error_envelopes():
    """Served graph-op contract (ISSUE PR 15): ``ppr``/``ase_embed``
    are first-class protocol ops (graph-scoped placement keys), a bad
    graph name or malformed query resolves to a structured 102 envelope
    AT THE DOOR (never raised across the serving boundary), and the new
    ops shed through the same 112/113 admission/deadline ladder as
    every other op."""
    import time

    from libskylark_tpu import serve
    from libskylark_tpu.graph import SimpleGraph
    from libskylark_tpu.serve import protocol
    from libskylark_tpu.utils import exceptions as ex

    assert "ppr" in protocol.OPS and "ase_embed" in protocol.OPS
    assert protocol.placement_key({"op": "ppr", "graph": "g"}) == "ppr:g"
    assert protocol.placement_key({"op": "ase_embed", "graph": "g"}) == "ase:g"

    G = SimpleGraph([(i, j) for i in range(4) for j in range(4, 9)])
    srv = serve.Server(
        serve.ServeParams(max_queue=2, warm_start=False, prime=False)
    )
    srv.register_graph("g", G, k=2)

    # 102 at the door: validation failures resolve without a worker.
    for req in (
        dict(op="ppr", graph="nope", seeds=[0]),
        dict(op="ppr", graph="g", seeds=[]),
        dict(op="ppr", graph="g", seeds=["ghost"]),
        dict(op="ppr", graph="g", seeds=[999]),
        dict(op="ase_embed", graph="nope", ids=[0]),
        dict(op="ase_embed", graph="g"),
        dict(op="ase_embed", graph="g", ids=[0], neighbors=[1]),
        dict(op="ase_embed", graph="g", neighbors=[]),
    ):
        resp = srv.submit(req).result()
        assert not resp["ok"], req
        assert resp["error"]["code"] == 102, (req, resp["error"])
        with pytest.raises(ex.InvalidParameters):
            serve.raise_for_error(resp)

    # 112: queue full (worker not started) sheds the third request;
    # the first admitted one carries a deadline for the 113 check below.
    fd = srv.submit(dict(op="ppr", graph="g", seeds=[2], deadline_ms=1))
    f1 = srv.submit(dict(op="ase_embed", graph="g", ids=[1]))
    shed = srv.call(op="ppr", graph="g", seeds=[1])
    assert not shed["ok"] and shed["error"]["code"] == 112
    with pytest.raises(ex.AdmissionError):
        serve.raise_for_error(shed)

    # 113: the lapsed deadline sheds at dispatch once the worker drains.
    time.sleep(0.05)
    srv.start()
    assert f1.result()["ok"]
    late = fd.result()
    srv.stop()
    assert not late["ok"] and late["error"]["code"] == 113
    with pytest.raises(ex.DeadlineExceededError):
        serve.raise_for_error(late)


@pytest.mark.graph
def test_graph_marker_registered_tier1():
    """Marker contract (ISSUE PR 15): the ``graph`` marker must stay a
    registered tier-1 mark with a hard per-test alarm — graph tests
    drive elastic folds and a live serve worker, either of which could
    otherwise wedge the tier-1 run.  Static over conftest so dropping
    the mark (or demoting it to slow) fails here."""
    import pathlib

    src = (pathlib.Path(__file__).parent / "conftest.py").read_text()
    assert '"graph": GRAPH_TIMEOUT_S' in src, (
        "the graph marker lost its _TIMEOUT_MARKS alarm entry"
    )
    assert "GRAPH_TIMEOUT_S = 120" in src
    assert '"markers",\n        "graph:' in src, (
        "the graph marker is no longer registered via addinivalue_line"
    )


@pytest.mark.train
def test_train_marker_registered_tier1():
    """Marker contract (ISSUE PR 17): the ``train`` marker must stay a
    registered tier-1 mark with a hard per-test alarm — distributed-
    training tests stream elastic folds and run multi-chunk kill/resume
    rounds, either of which could otherwise wedge the tier-1 run."""
    import pathlib

    src = (pathlib.Path(__file__).parent / "conftest.py").read_text()
    assert '"train": TRAIN_TIMEOUT_S' in src, (
        "the train marker lost its _TIMEOUT_MARKS alarm entry"
    )
    assert "TRAIN_TIMEOUT_S = 180" in src
    assert '"markers",\n        "train:' in src, (
        "the train marker is no longer registered via addinivalue_line"
    )


@pytest.mark.train
def test_snapshot_folds_train_counter_group():
    """Static contract check (ISSUE PR 17): ``telemetry.snapshot()``
    must fold the ``train.*`` counters into a ``"train"`` group — the
    distributed trainer's runs/iterations/consensus/escalations surface
    docs/distributed_training.md points operators at.  Conditional like
    the router/autoscale groups: absent until a trainer ran."""
    import importlib
    import inspect

    report = importlib.import_module("libskylark_tpu.telemetry.report")
    snap_src = inspect.getsource(report.snapshot)
    assert '"train"' in snap_src and "train." in snap_src, (
        "telemetry.snapshot() no longer folds the train.* counter "
        'group into snap["train"] (docs/distributed_training.md contract)'
    )
