"""Unified telemetry: structured spans, metrics registry, JSONL run ledger.

The observability layer the reference never had (its ``utility/timer.hpp``
macros reduce wall timers over MPI ranks and nothing else): one
process-wide :class:`Registry` of counters/gauges/histograms, nestable
:func:`span` contexts (wall time under the ``PhaseTimer`` sync
discipline, profiler regions via ``utils.profiling.region``), and a
monotonically sequenced JSONL event sink — the *run ledger* — with the
schema ``{ts, seq, pid, kind, name, attrs}``.

Wired through every hot seam: plan-cache hits/misses/compiles
(``plans``), streaming chunk spans + prefetch overlap (``streaming``),
recovery-ladder attempts (``guard``), checkpoint save/restore
(``resilient``), and per-chunk solver progress; every ``(x, info)``
solver entrypoint closes its run with a :func:`run_summary` event.

What is gated and what is not.  :func:`span` ALWAYS opens the profiler
annotation ``skylark:<name>`` (``utils.profiling.region``; ``PhaseTimer``
phases open the same one), so any ``jax.profiler`` trace of a run shows
the program's stages on the device's clock — ``skylark:<entry>`` around
a public call, ``skylark:<layer>.<stage>`` inside it.  Everything else
waits for ``SKYLARK_TELEMETRY`` (default OFF, read per call): disabled,
a span is the bare annotation and every other entry point returns
before allocating — no ledger, no registry write, no sync, no
``jax.monitoring`` listener, no ``atexit`` hook; runs are bit-identical
to a build without this package.  ``SKYLARK_TELEMETRY_DIR`` (or
:func:`configure`, or the CLIs' ``--telemetry-dir``) points the ledger
at a directory; without it events still count in the registry.

End of run: :func:`snapshot` folds the registry with ``plans.stats()``,
the prefetch overlap ratio, and the guard/checkpoint counter groups;
:func:`report` reduces counters min/max/avg over ``jax.distributed``
processes under the same ``process_allgather`` + CRC-signature contract
as ``utils.timer.timer_report``.  See ``docs/observability.md``.

The fleet observability plane rides on top: request-scoped traces
minted at serve admission (:mod:`.trace` — TraceContext, the bounded
flight recorder, cross-layer :func:`trace_event` attachment),
``snapshot(fleet=True)`` cross-host aggregation (:mod:`.fleet` —
allgathered registries whose merged counters SUM over ranks, plus the
epoch-fenced ``host-*/progress.jsonl`` ledger fold), and the
Prometheus text exposition (:mod:`.exposition`) the serve ``/metrics``
endpoint and ``skylark-top`` scrape.
"""

from .config import enabled, ledger_dir
from .exposition import prometheus_text
from .fleet import fleet_snapshot, fold_ledgers, merge_snapshots
from .ledger import close, configure, emit, event, flush, ledger_path
from .phases import PHASES, enable_phase_buckets, observe_phase, phases_enabled
from .registry import (
    LOCK,
    REGISTRY,
    Registry,
    enable_buckets,
    inc,
    observe,
    reset,
    set_gauge,
)
from .report import report, run_summary, snapshot
from .slo import observe_slo, reset_slo, slo_report
from .timeline import (
    reset_timeline,
    timeline_state,
    timeline_tick,
    timeline_windows,
)
from .spans import Span, span
from .trace import (
    RECORDER,
    FlightRecorder,
    TraceContext,
    activate,
    drain_traces,
    dump_traces,
    error_event,
    get_trace,
    is_violating,
    mint,
    trace_enabled,
    trace_event,
    trace_ids,
)
from .trace import finish as finish_trace

__all__ = [
    "enabled",
    "ledger_dir",
    "configure",
    "event",
    "emit",
    "ledger_path",
    "flush",
    "close",
    "Registry",
    "REGISTRY",
    "LOCK",
    "inc",
    "set_gauge",
    "observe",
    "enable_buckets",
    "reset",
    # phase clock + SLO engine + timeline ring
    "PHASES",
    "phases_enabled",
    "observe_phase",
    "enable_phase_buckets",
    "observe_slo",
    "slo_report",
    "reset_slo",
    "timeline_tick",
    "timeline_windows",
    "timeline_state",
    "reset_timeline",
    "span",
    "Span",
    "snapshot",
    "run_summary",
    "report",
    # tracing + flight recorder
    "TraceContext",
    "FlightRecorder",
    "RECORDER",
    "mint",
    "trace_enabled",
    "is_violating",
    "activate",
    "trace_event",
    "error_event",
    "finish_trace",
    "get_trace",
    "trace_ids",
    "drain_traces",
    "dump_traces",
    # fleet aggregation + exposition
    "merge_snapshots",
    "fold_ledgers",
    "fleet_snapshot",
    "prometheus_text",
]
