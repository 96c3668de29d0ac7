"""Test configuration: force an 8-device virtual CPU mesh and float64.

Multi-chip behavior is tested on virtual CPU devices the way the reference
tests multi-node behavior with `mpirun -np K` on one box
(`tests/unit/CMakeLists.txt:11-38`).  x64 is enabled for numerical-parity
checks against the reference's double-precision semantics.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)
# Entry points place the persistent compilation cache in the checkout
# (utils.compile_cache); tests compile fresh so a stale or foreign cache
# entry can never decide a result.
jax.config.update("jax_enable_compilation_cache", False)

import signal  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Fault-injection tests must never hang the tier-1 run (a botched resume
# path could loop forever waiting on a checkpoint that never appears), so
# every ``faults``-marked test gets a hard per-test alarm.  They stay
# inside the ``-m 'not slow'`` selection on purpose: the recovery paths
# run on every PR.  ``streaming`` tests get the same guard for the same
# reason — a stuck prefetch queue or an unfinished producer thread would
# otherwise block the run forever.
FAULTS_TIMEOUT_S = 120
STREAMING_TIMEOUT_S = 120
GUARD_TIMEOUT_S = 120
TELEMETRY_TIMEOUT_S = 120
# Multi-process elastic streaming runs three real jax.distributed worlds
# back-to-back (reference run, kill-one-rank run, resume run), each with
# its own formation timeout — the alarm must cover the worst-case sum.
DISTRIBUTED_STREAMING_TIMEOUT_S = 900
# Host-level chaos tests (rank death, stragglers, stale-epoch writers,
# repartitioned resumes) simulated in ONE process; real multi-process
# chaos rides the distributed_streaming slow tier instead.
CHAOS_TIMEOUT_S = 120
# Pallas kernel tests run in interpret mode on CPU CI (the compiled
# kernels only exist on TPU); interpret mode executes the kernel body
# as traced jax ops, so a mis-sized grid or a runaway scalar loop
# would otherwise stall the tier-1 run.
KERNELS_TIMEOUT_S = 120
# Adaptive-policy tests run real (small) guarded solves to mature
# profile stores, plus subprocess determinism checks; a wedged store
# merge or a hung subprocess must not stall the tier-1 run.
POLICY_TIMEOUT_S = 120
# Serve-layer tests run a real worker thread behind a blocking queue
# (plus an HTTP loopback); a worker that never drains, a future that
# never resolves, or a leaked socket must not stall the tier-1 run.
SERVE_TIMEOUT_S = 120
# Overlap tests run full streaming passes twice (overlapped vs serial)
# plus kill-resume rounds under donation; a fold that never syncs or a
# resume that re-opens a wedged source must not stall the tier-1 run.
OVERLAP_TIMEOUT_S = 120
# Trace-plane tests drive live servers (worker thread + HTTP scrapers)
# and fleet folds; a scrape that deadlocks against the worker must not
# stall the tier-1 run.
TRACE_TIMEOUT_S = 120
# Fleet tests run multi-worker servers, a router front door with
# heartbeat polling, and sharded-dispatch parity probes over virtual
# devices; a placement that never resolves or a worker pinned to a
# wedged device must not stall the tier-1 run.
FLEET_TIMEOUT_S = 120
# Refine tests drive host-gated refinement sweeps (certified gates,
# stagnation/ladder fallback, policy earning); a sweep loop that never
# meets its gate must not stall the tier-1 run.
REFINE_TIMEOUT_S = 120
# Graph tests fold streamed edge blocks through elastic runs and drive
# served PPR/embed queries behind the worker thread; a wedged fold or
# an unresolved future must not stall the tier-1 run.
GRAPH_TIMEOUT_S = 120
# Distributed-training tests stream feature blocks through elastic
# folds, run multi-chunk ADMM under the resilient runner (including
# kill/resume rounds), and simulate consensus merges across ranks in
# one process; a wedged stream or a resume that waits on a checkpoint
# that never lands must not stall the tier-1 run.
TRAIN_TIMEOUT_S = 180
# QoS tests drive weighted-fair tenant lanes and token-bucket quotas
# through live servers under concurrent multi-tenant load; a lane the
# scheduler never visits or a future that never resolves must not
# stall the tier-1 run.
QOS_TIMEOUT_S = 120
# Result-cache tests drive the front-door cache across live-registry
# epoch bumps behind the worker thread; a wedged invalidation or an
# unresolved future must not stall the tier-1 run.
CACHE_TIMEOUT_S = 120
# Durability tests journal real registries through fsync'd appends,
# SIGKILL child replicas mid-update-stream, and replay recovery; a
# child that never dies or a recover that waits on a journal handle
# must not stall the tier-1 run.
DURABILITY_TIMEOUT_S = 120

_TIMEOUT_MARKS = {
    "faults": FAULTS_TIMEOUT_S,
    "streaming": STREAMING_TIMEOUT_S,
    "guard": GUARD_TIMEOUT_S,
    "telemetry": TELEMETRY_TIMEOUT_S,
    "distributed_streaming": DISTRIBUTED_STREAMING_TIMEOUT_S,
    "chaos": CHAOS_TIMEOUT_S,
    "kernels": KERNELS_TIMEOUT_S,
    "policy": POLICY_TIMEOUT_S,
    "serve": SERVE_TIMEOUT_S,
    "overlap": OVERLAP_TIMEOUT_S,
    "trace": TRACE_TIMEOUT_S,
    "fleet": FLEET_TIMEOUT_S,
    "refine": REFINE_TIMEOUT_S,
    "graph": GRAPH_TIMEOUT_S,
    "train": TRAIN_TIMEOUT_S,
    "qos": QOS_TIMEOUT_S,
    "cache": CACHE_TIMEOUT_S,
    "durability": DURABILITY_TIMEOUT_S,
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "faults: fault-injection / resilience tests (preemption, corrupt "
        "checkpoints, transient IO); tier-1, guarded by a per-test "
        f"{FAULTS_TIMEOUT_S}s timeout",
    )
    config.addinivalue_line(
        "markers",
        "streaming: out-of-core streaming engine tests (partial sketches, "
        "prefetch pipeline, resumable passes) on small synthetic data; "
        f"tier-1, guarded by a per-test {STREAMING_TIMEOUT_S}s timeout",
    )
    config.addinivalue_line(
        "markers",
        "perf: performance/latency assertions (wall-clock thresholds, "
        "machine-sensitive); NOT tier-1 — auto-skipped unless "
        "SKYLARK_RUN_PERF=1",
    )
    config.addinivalue_line(
        "markers",
        "guard: numerical-health guard tests (sentinels, certification, "
        "recovery ladder, fault-injected recovery); tier-1, guarded by a "
        f"per-test {GUARD_TIMEOUT_S}s timeout",
    )
    config.addinivalue_line(
        "markers",
        "telemetry: observability-layer tests (spans, metrics registry, "
        "JSONL run ledger, run_summary contract); tier-1, guarded by a "
        f"per-test {TELEMETRY_TIMEOUT_S}s timeout",
    )
    config.addinivalue_line(
        "markers",
        "distributed_streaming: multi-process elastic streaming tests "
        "(kill-one-rank resume over real jax.distributed worlds); slow "
        f"tier, guarded by a per-test {DISTRIBUTED_STREAMING_TIMEOUT_S}s "
        "timeout",
    )
    config.addinivalue_line(
        "markers",
        "chaos: host-level chaos tests (rank death, stragglers, stale-"
        "epoch fencing, repartition-on-resume) simulated in one process; "
        f"tier-1, guarded by a per-test {CHAOS_TIMEOUT_S}s timeout",
    )
    config.addinivalue_line(
        "markers",
        "kernels: Pallas kernel tests (window scatter, gather, fused "
        "stream chunks) in interpret mode on CPU CI; tier-1, guarded "
        f"by a per-test {KERNELS_TIMEOUT_S}s timeout",
    )
    config.addinivalue_line(
        "markers",
        "policy: adaptive execution-policy tests (profile store, routing "
        "decisions, warm start, bit-parity contract); tier-1, guarded by "
        f"a per-test {POLICY_TIMEOUT_S}s timeout",
    )
    config.addinivalue_line(
        "markers",
        "serve: sketch-serving layer tests (cross-request coalescing, "
        "bitwise request isolation, admission/deadline shedding, "
        "transports); tier-1, guarded by a per-test "
        f"{SERVE_TIMEOUT_S}s timeout",
    )
    config.addinivalue_line(
        "markers",
        "overlap: async device-overlap streaming tests (overlapped vs "
        "serial bitwise parity, kill-resume under donation, sync-point "
        "discipline); tier-1, guarded by a per-test "
        f"{OVERLAP_TIMEOUT_S}s timeout",
    )
    config.addinivalue_line(
        "markers",
        "trace: fleet observability-plane tests (request tracing, flight "
        "recorder, cross-host aggregation, exposition endpoints); tier-1, "
        f"guarded by a per-test {TRACE_TIMEOUT_S}s timeout",
    )
    config.addinivalue_line(
        "markers",
        "fleet: fleet-scale serving tests (device-parallel dispatch "
        "parity, replicated workers, router placement / membership / "
        "failover); tier-1, guarded by a per-test "
        f"{FLEET_TIMEOUT_S}s timeout",
    )
    config.addinivalue_line(
        "markers",
        "refine: certified mixed-precision refinement tests (route-OFF "
        "bitwise parity, certified convergence, stagnation/ladder "
        "fallback, served cond-est, quasirandom sketch interchange); "
        f"tier-1, guarded by a per-test {REFINE_TIMEOUT_S}s timeout",
    )
    config.addinivalue_line(
        "markers",
        "graph: graph-analytics tests (streamed edge-list folds, chained "
        "sharded sketches, streaming ASE, served PPR/embed queries); "
        f"tier-1, guarded by a per-test {GRAPH_TIMEOUT_S}s timeout",
    )
    config.addinivalue_line(
        "markers",
        "train: distributed kernel-machine training tests (world=1 "
        "bitwise parity, simulated-rank consensus, kill/resume through "
        "the ADMM loop, guard recovery mid-stream); tier-1, guarded by "
        f"a per-test {TRAIN_TIMEOUT_S}s timeout",
    )
    config.addinivalue_line(
        "markers",
        "qos: multi-tenant QoS tests (deficit-weighted tenant lanes, "
        "token-bucket quota sheds, tenant-stamped envelopes/counters); "
        f"tier-1, guarded by a per-test {QOS_TIMEOUT_S}s timeout",
    )
    config.addinivalue_line(
        "markers",
        "cache: front-door result-cache tests (bitwise hit parity, "
        "epoch-bump invalidation, LRU/byte bounds, fleet hit sharing); "
        f"tier-1, guarded by a per-test {CACHE_TIMEOUT_S}s timeout",
    )
    config.addinivalue_line(
        "markers",
        "durability: serve durability tests (write-ahead journal, "
        "bitwise crash recovery, SIGKILL chaos drills, exactly-once "
        "idempotency across failover); tier-1, guarded by a per-test "
        f"{DURABILITY_TIMEOUT_S}s timeout",
    )


def pytest_collection_modifyitems(config, items):
    # perf tests assert wall-clock behavior that flakes on loaded CI
    # hosts; tier-1 selects with -m 'not slow', which would include
    # them, so they gate on an explicit env opt-in instead.
    if os.environ.get("SKYLARK_RUN_PERF") == "1":
        return
    skip = pytest.mark.skip(
        reason="perf test: machine-sensitive timing; set SKYLARK_RUN_PERF=1"
    )
    for item in items:
        if item.get_closest_marker("perf") is not None:
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _marked_timeout(request):
    limits = [
        (name, seconds)
        for name, seconds in _TIMEOUT_MARKS.items()
        if request.node.get_closest_marker(name) is not None
    ]
    if not limits:
        yield
        return
    name, seconds = min(limits, key=lambda kv: kv[1])

    def _alarm(signum, frame):
        raise TimeoutError(
            f"{name} test exceeded {seconds}s hard timeout"
        )

    old = signal.signal(signal.SIGALRM, _alarm)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture
def rng():
    return np.random.default_rng(0)
