"""Phase timers with optional cross-process min/max/avg aggregation.

≙ ``SKYLARK_TIMER_{DECLARE,INITIALIZE,RESTART,ACCUMULATE,PRINT}``
(``utility/timer.hpp:6-70``): named accumulating wall timers.  The
reference's PRINT reduces min/max/avg over ALL MPI ranks — the world
communicator (``utility/timer.hpp:44-66``); here
``timer_report(..., distributed=True)`` gathers each process's phase
scalars with ``multihost_utils.process_allgather`` (a job-global
collective over every ``jax.distributed`` process, exactly the world-
communicator semantics — it cannot be scoped to a sub-mesh, so the API
deliberately takes a boolean, not a mesh) and prints the same
three-column reduction.  Without it the report stays per-process.
Device work is made observable by assigning the phase handle's
``result`` (blocked on at phase exit — the reference's barrier).
Every phase also opens ``profiling.region(name)``, the annotation
``telemetry.span`` opens, so a profiler trace shows ``skylark:sweep``
around the sweep's device work on the same clock.
"""

from __future__ import annotations

import time
from collections import defaultdict
from contextlib import contextmanager

import jax
import numpy as np

from . import profiling

__all__ = ["PhaseTimer", "timer_report", "aggregate_report"]


class _PhaseHandle:
    """Set ``.result`` inside the phase so device work is synced on exit."""

    result = None


class PhaseTimer:
    """Accumulating named phase timers (one instance per algorithm run).

    Usage::

        t = PhaseTimer()
        with t.phase("transform") as ph:
            ph.result = S.apply(X)   # blocked on at phase exit
        print(t.report())            # or t.report(mesh=mesh) multi-host

    JAX dispatch is asynchronous: without assigning ``ph.result`` the
    phase records only dispatch time, not device time.
    """

    def __init__(self, sync: bool = True):
        self.totals: dict[str, float] = defaultdict(float)
        self.counts: dict[str, int] = defaultdict(int)
        self.sync = sync

    @contextmanager
    def phase(self, name: str):
        handle = _PhaseHandle()
        with profiling.region(name):
            t0 = time.perf_counter()
            try:
                yield handle
            finally:
                if self.sync and handle.result is not None:
                    jax.block_until_ready(handle.result)
                self.totals[name] += time.perf_counter() - t0
                self.counts[name] += 1

    def report(self, distributed: bool = False) -> str:
        return timer_report(self.totals, self.counts, distributed=distributed)


def timer_report(totals, counts=None, distributed: bool = False) -> str:
    """Phase-timer report.

    Default: local total/calls/avg table (per-process, ≙ timer.hpp PRINT
    on one rank).  With ``distributed=True``, EVERY process of the
    ``jax.distributed`` job must call with the SAME phase names (the
    reference's PRINT has the same collective contract — all world ranks
    enter the reduction; ``process_allgather`` is job-global and cannot
    be scoped to a sub-mesh): phase totals are all-gathered across
    processes and reported as min/max/avg over ranks.  In a
    single-process job (tests, one host) the gathered axis has length 1
    and min = max = avg = the local totals.
    """
    if not distributed:
        lines = [f"{'phase':<24}{'total(s)':>12}{'calls':>8}{'avg(s)':>12}"]
        for name in sorted(totals):
            total = totals[name]
            n = (counts or {}).get(name, 1) or 1
            lines.append(f"{name:<24}{total:>12.4f}{n:>8}{total / n:>12.4f}")
        return "\n".join(lines)

    from jax.experimental import multihost_utils

    names = sorted(totals)
    # The gather below aligns columns positionally, so every process must
    # bring the SAME phase-name list; a rank that recorded a different set
    # would silently misalign (or crash on a shape mismatch deep inside
    # the gather).  Validate first: gather a stable hash of the name list
    # and fail loudly on disagreement.
    import zlib

    sig = np.asarray(
        [zlib.crc32("\x00".join(names).encode()), len(names)], np.int64
    )
    sigs = np.atleast_2d(np.asarray(multihost_utils.process_allgather(sig)))
    if not (sigs == sigs[0]).all():
        raise RuntimeError(
            "timer_report(distributed=True): processes recorded different "
            f"phase-name sets (this rank has {names}); every rank must time "
            "the same phases — the reference's SKYLARK_TIMER_PRINT has the "
            "same world-collective contract (utility/timer.hpp:44-66)"
        )
    vec = np.asarray([totals[n] for n in names], np.float64)
    cnt = np.asarray([(counts or {}).get(n, 1) or 1 for n in names], np.int64)
    stacked = np.atleast_2d(np.asarray(multihost_utils.process_allgather(vec)))
    counts2d = np.atleast_2d(np.asarray(multihost_utils.process_allgather(cnt)))
    return aggregate_report(names, stacked, counts2d)


def aggregate_report(names, stacked, counts2d=None) -> str:
    """min/max/avg-over-ranks table from ``stacked`` (P, k) phase totals
    (≙ the MPI_Reduce triple of ``utility/timer.hpp:44-66``).  Split from
    :func:`timer_report` so the multi-rank reduction is testable without
    a real multi-process run."""
    P = stacked.shape[0]
    lines = [
        f"{'phase':<24}{'min(s)':>12}{'max(s)':>12}{'avg(s)':>12}"
        f"{'calls':>8}  (over {P} process{'es' if P != 1 else ''})"
    ]
    for j, name in enumerate(names):
        col = stacked[:, j]
        calls = int(counts2d[:, j].max()) if counts2d is not None else 1
        lines.append(
            f"{name:<24}{col.min():>12.4f}{col.max():>12.4f}"
            f"{col.mean():>12.4f}{calls:>8}"
        )
    return "\n".join(lines)
