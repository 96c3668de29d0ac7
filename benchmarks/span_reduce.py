"""The program's spans against the device's lines: idle time by stage,
device time by the stage that launched it, host events by stage.

The program opens a ``TraceAnnotation`` named ``skylark:<entry>`` around
each public call and ``skylark:<layer>.<stage>`` around each stage of it
(``docs/observability.md``); they land on the host plane of the
profiler's trace, on the clock of the device's lines.  Everything here
works on the plain event lists ``(name, start_ns, duration_ns)`` of
``trace_reduce`` (its ``merge``, ``clip``, ``gaps``), so
``tests/benchmark/test_span_readers.py`` checks the arithmetic on
hand-made traces.  A trace of a program without spans gives no interval
to any pattern: the readers then return None and the metric is left out.
"""

from __future__ import annotations

import re

from trace_reduce import clip, gaps, merge, strip_id

SPAN_PREFIX = "skylark:"
PJIT = re.compile(r"^PjitFunction\((.*)\)$")


def spans(host, pattern: str):
    """The host's span events whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return [ev for ev in host
            if ev[0].startswith(SPAN_PREFIX) and rx.search(ev[0])]


def has_spans(host) -> bool:
    return any(ev[0].startswith(SPAN_PREFIX) for ev in host)


def cover(events, lo, hi, invert: bool = False):
    """Sorted, disjoint intervals of ``[lo, hi]`` inside some event (two
    nested events count once); with ``invert``, inside none."""
    if invert:
        return sorted(gaps(events, lo, hi))
    return [tuple(iv) for iv in merge(clip(events, lo, hi))]


def overlap_ns(a, b) -> int:
    """Nanoseconds that lie in both of two sorted, disjoint interval lists."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_in_spans_ns(device_events, host, pattern, lo, hi, invert=False) -> int:
    """Nanoseconds of ``[lo, hi]`` in which no device event ran and the
    host was inside a span matching ``pattern`` (``invert``: inside none)."""
    idle = sorted(gaps(device_events, lo, hi))
    return overlap_ns(idle, cover(spans(host, pattern), lo, hi, invert))


def inside(at, intervals) -> bool:
    return any(s <= at < e for s, e in intervals)


def count_events(host, event: str, pattern, lo, hi) -> int:
    """Host events of ``[lo, hi]`` whose name matches ``event`` and that
    start inside a span matching ``pattern`` (anywhere when it is None)."""
    rx = re.compile(event)
    within = None if pattern is None else cover(spans(host, pattern), lo, hi)
    return sum(
        1 for name, s, _ in host
        if lo <= s < hi and rx.search(name)
        and (within is None or inside(s, within)))


def module_of(function: str) -> str:
    """The XLA module that a jitted ``function`` runs as: JAX names it
    ``jit_`` + the function's name with every character that is no
    letter, digit or ``_`` made ``_`` (``<lambda>`` -> ``jit__lambda_``)."""
    return "jit_" + re.sub(r"\W", "_", function)


def launches(host, marker: str):
    """``(module name, start_ns)`` of every program the host enqueued, in
    order.  A launch is an event matching ``marker`` (the runtime's own
    span around one enqueue); its program is that of the innermost
    ``PjitFunction(<f>)`` around it.  So a ``PjitFunction`` that launches
    nothing (one called while tracing) gives none, and one that holds
    another is not counted for the inner one's launch."""
    rx = re.compile(marker)
    calls = sorted((s, s + d, m.group(1)) for name, s, d in host
                   if (m := PJIT.match(name)))
    out = []
    for s in sorted(s for name, s, _ in host if rx.search(name)):
        # innermost: the latest-starting call that is still open at s
        around = [c for c in calls if c[0] <= s < c[1]]
        out.append((module_of(max(around)[2]) if around else None, s))
    return out


def launched_ns(modules, host, pattern: str, marker: str, lo, hi):
    """Device nanoseconds of the module executions whose launch started
    in ``[lo, hi]`` inside a span matching ``pattern``, and the share of
    the window's module time that found its launch.

    One chip runs its programs in the order they were enqueued, and the
    trace holds both lines from its start: the k-th launch of the trace
    is the k-th event of the module line.  The names check it: a pair
    counts as found only where the module is the launching function's
    ``jit_<f>``, so a launch or an execution that the trace lacks shifts
    every later pair and the share falls.  An execution belongs to the
    window, and to a span, by where its *launch* starts on the host's
    clock: it may run after the span has closed, and the device's clock
    need not agree with the host's to the millisecond (on a v5e it was
    0.2 to 1.4 ms ahead)."""
    within = cover(spans(host, pattern), lo, hi)
    started = launches(host, marker)
    ran = sorted(modules, key=lambda ev: ev[1])
    total = found = hit = 0
    for (function, at), (name, _, d) in zip(started, ran):
        if lo <= at < hi:
            total += d
            if strip_id(name) == function:
                found += d
                if inside(at, within):
                    hit += d
    # executions past the last launch: nobody launched them
    total += sum(d for _, s, d in ran[len(started):] if lo <= s < hi)
    return hit, (found / total if total else 0.0)
