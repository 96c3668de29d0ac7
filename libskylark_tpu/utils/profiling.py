"""Device-level tracing (the TPU replacement for the reference's
compile-time profiler macros, SURVEY §5: "jax.profiler traces + per-phase
wall timers").

``PhaseTimer`` (``utils.timer``) covers the wall-clock side; this module
wraps ``jax.profiler`` for op-level traces viewable in XProf/TensorBoard.

This is the one place that builds a ``TraceAnnotation``:
``telemetry.span`` and ``PhaseTimer.phase`` both open :func:`region`, so
the program's spans lie on the profiler's clock, beside the device's
lines, under one naming rule — ``skylark:<entry>`` for the span of a
whole public call, ``skylark:<layer>.<stage>`` for a stage of it
(``docs/observability.md`` lists them).  With no profiler session an
annotation costs a fraction of a microsecond and leaves nothing behind.
"""

from __future__ import annotations

from contextlib import contextmanager

import jax

__all__ = ["trace", "annotate", "region"]

PREFIX = "skylark:"  # every span the program opens, and nothing else


@contextmanager
def trace(logdir: str):
    """Capture a device trace into ``logdir`` (open with xprof/TensorBoard).

    Usage::

        with profiling.trace("/tmp/skylark-trace"):
            model = solver.train(X, y)
    """
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named region inside a trace (≙ the reference's per-phase timer
    labels); usable as decorator or context manager."""
    return jax.profiler.TraceAnnotation(name)


def region(name: str):
    """The program's span ``name`` as it stands in a trace:
    ``annotate("skylark:" + name)``."""
    return annotate(PREFIX + name)
