"""Nestable spans: profiler annotations always, wall time + ledger
events when telemetry is on.

Every span opens ``utils.profiling.region(name)`` — the
``TraceAnnotation`` ``skylark:<name>`` — whether or not
``SKYLARK_TELEMETRY`` is set, so a profiler trace captured around a run
shows the program's stages on the device trace's clock.  With telemetry
off that is ALL a span is: :func:`span` hands back the bare annotation —
no event, no counter, no sync, no listener, nothing allocated beside it.

With telemetry on a span is the analogue of one ``PhaseTimer`` phase,
and keeps its sync discipline: assign the span handle's ``result``
inside the region and the exit path runs ``jax.block_until_ready`` on it
before reading the clock, so the span measures DEVICE time, not dispatch
time.  Nesting is tracked per thread: every span records its parent's
id (the ``seq`` of the parent's ``span_start`` event) so the ledger
reconstructs the span tree.  ``span_end`` also says what JAX built while
the span was open — ``traces``/``trace_s`` (jaxpr traces),
``lowerings``/``lower_s`` (jaxpr → MLIR), ``compiles``/``compile_s``
(backend compile requests, persistent-cache fetches among them) and
``cache_hits`` — from ``jax.monitoring``'s events; a key that would read
0 is left out.  The listener is registered by the first enabled span,
never at import.
"""

from __future__ import annotations

import collections
import threading
import time

import jax

from ..utils import profiling
from . import config
from .ledger import event
from .registry import REGISTRY

__all__ = ["span", "Span"]

_LOCAL = threading.local()

# jax.monitoring duration events -> (count key, seconds key) of span_end
_BUILD_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": ("traces", "trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("lowerings", "lower_s"),
    "/jax/core/compile/backend_compile_duration": ("compiles", "compile_s"),
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_LISTEN_LOCK = threading.Lock()
_listening = False


def _stack() -> list:
    stack = getattr(_LOCAL, "spans", None)
    if stack is None:
        stack = _LOCAL.spans = []
    return stack


def _on_duration(name: str, secs: float, **_):
    keys = _BUILD_EVENTS.get(name)
    if keys is not None:
        # JAX traces, lowers and compiles on the calling thread: the open
        # spans of this thread are the ones the work happened under.
        for sp in _stack():
            sp.built[keys[0]] += 1
            sp.built[keys[1]] += secs


def _on_event(name: str, **_):
    if name == _CACHE_HIT_EVENT:
        for sp in _stack():
            sp.built["cache_hits"] += 1


def _listen() -> None:
    """Register the ``jax.monitoring`` listeners, once a process."""
    global _listening
    if _listening:
        return
    with _LISTEN_LOCK:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            jax.monitoring.register_event_listener(_on_event)
            _listening = True


class Span:
    """One live span; ``attrs`` may be amended inside the region (the
    ``span_end`` event re-reads them, so late facts — rows folded,
    batches seen — land on the closing record)."""

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.result = None
        self.id = None
        self.seconds = None
        self.built = collections.Counter()  # what JAX built inside

    def __enter__(self):
        _listen()
        stack = _stack()
        start_attrs = dict(self.attrs)
        if stack:
            start_attrs["parent"] = stack[-1].id
        start_attrs["depth"] = len(stack)
        self._t0 = time.perf_counter()
        self.id = event("span_start", self.name, start_attrs)
        stack.append(self)
        self._region = profiling.region(self.name)
        self._region.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self.result is not None:
            jax.block_until_ready(self.result)
        self._region.__exit__(exc_type, exc, tb)
        self.seconds = time.perf_counter() - self._t0
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        REGISTRY.inc(f"span.{self.name}.calls")
        REGISTRY.inc(f"span.{self.name}.seconds", self.seconds)
        end_attrs = dict(self.attrs)
        end_attrs["span"] = self.id
        end_attrs["seconds"] = round(self.seconds, 6)
        for key, value in self.built.items():
            REGISTRY.inc(f"span.{self.name}.{key}", value)
            end_attrs[key] = round(value, 6)
        if exc_type is not None:
            end_attrs["error"] = exc_type.__name__
        event("span_end", self.name, end_attrs)
        return False


def span(name: str, **attrs):
    """Open a nestable span (context manager).

    Usage::

        with telemetry.span("stream.chunk", chunk=b0) as sp:
            sp.result = acc        # blocked on at exit (PhaseTimer rule)
            sp.attrs["rows"] = k   # lands on the span_end event

    Disabled (``SKYLARK_TELEMETRY=0``): returns the bare profiler
    annotation ``skylark:<name>`` — ``result`` may still be assigned to
    it (never synced); ``attrs`` is the enabled span's alone.
    """
    if not config.enabled():
        return profiling.region(name)
    return Span(name, attrs)
