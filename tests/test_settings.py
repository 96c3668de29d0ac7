"""The census of environment settings the library reads.

Every independently settable value doubles what tests and the benchmark
would have to cover, so the list is kept where a reviewer sees it grow:
a PR that adds a ``SKYLARK_*`` name under ``libskylark_tpu/`` has to
add it here too, and a name that was removed cannot come back unnoticed.
"""

import pathlib
import re

LIBRARY = pathlib.Path(__file__).resolve().parent.parent / "libskylark_tpu"

# ``SKYLARK_TIMER``, ``SKYLARK_TIMER_`` and ``SKYLARK_TIMER_PRINT`` are
# the reference's macro names as ``utils/timer.py`` cites them, not
# settings; they are listed because the census is the plain search.
SETTINGS = {
    "SKYLARK_CACHE",
    "SKYLARK_CACHE_MAX_BYTES",
    "SKYLARK_CACHE_MAX_ENTRIES",
    "SKYLARK_COLLECTIVE_TIMEOUT_S",
    "SKYLARK_FRFT_GEMM",
    "SKYLARK_GUARD",
    "SKYLARK_GUARD_COND_MAX",
    "SKYLARK_GUARD_MAX_RETRIES",
    "SKYLARK_HTTP_TIMEOUT_S",
    "SKYLARK_IDEM_WINDOW",
    "SKYLARK_JOURNAL_COMPACT_EVERY",
    "SKYLARK_NO_FRFT_GEMM",
    "SKYLARK_NO_FUSED_CHUNKS",
    "SKYLARK_NO_OVERLAP",
    "SKYLARK_NO_PALLAS",
    "SKYLARK_NO_PLANS",
    "SKYLARK_NO_PPT_DFT",
    "SKYLARK_NO_SRHT_GEMM",
    "SKYLARK_PALLAS_GATHER",
    "SKYLARK_PALLAS_WINDOW",
    "SKYLARK_PHASES",
    "SKYLARK_PLAN_CACHE_SIZE",
    "SKYLARK_PLAN_DONATE",
    "SKYLARK_POLICY",
    "SKYLARK_POLICY_BF16",
    "SKYLARK_POLICY_DIR",
    "SKYLARK_POLICY_MIN_SAMPLES",
    "SKYLARK_POLICY_WARM_PLANS",
    "SKYLARK_PPT_DFT",
    "SKYLARK_QOS_QUANTUM",
    "SKYLARK_QOS_QUOTAS",
    "SKYLARK_QOS_QUOTA_BURST",
    "SKYLARK_QOS_QUOTA_RPS",
    "SKYLARK_QOS_TENANT_METRICS_MAX",
    "SKYLARK_QOS_WEIGHTS",
    "SKYLARK_SERVE_SHARD",
    "SKYLARK_SERVE_SHARD_MIN_FLOPS",
    "SKYLARK_SLO",
    "SKYLARK_SLO_BURN",
    "SKYLARK_SLO_WINDOW",
    "SKYLARK_TELEMETRY",
    "SKYLARK_TELEMETRY_DIR",
    "SKYLARK_TELEMETRY_FLEET_ROOT",
    "SKYLARK_TIMELINE_CAPACITY",
    "SKYLARK_TIMELINE_INTERVAL_S",
    "SKYLARK_TIMER",
    "SKYLARK_TIMER_",
    "SKYLARK_TIMER_PRINT",
    "SKYLARK_TRACE",
    "SKYLARK_TRACE_CAPACITY",
    "SKYLARK_WINDOW_CHUNK",
    "SKYLARK_WINDOW_MIN_GATHER",
    "SKYLARK_WINDOW_MIN_K",
}


def test_library_reads_exactly_these_settings():
    found = set()
    for path in LIBRARY.rglob("*.py"):
        found.update(re.findall(r"SKYLARK_[A-Z0-9_]+", path.read_text()))
    assert found == SETTINGS, (
        f"added: {sorted(found - SETTINGS)}, gone: {sorted(SETTINGS - found)}"
    )
