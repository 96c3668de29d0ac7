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

Beside the spans stand the **program records**.  A trace's device lines
name an operation by its HLO instruction (``%fusion.12``) and carry no
``op_name``, so device time by the program's own named scopes
(``jax.named_scope``) needs the map *instruction -> op_name* of the
program that really ran.  The hot programs are called through
:func:`launch`, which, only while a profiler session is open, notes the
jitted function and the abstract signature of the call;
:func:`records` later lowers each noted program again and reads the map
and the compiler's byte counts from it (``docs/observability.md``,
"Program records").  With no session nothing is noted: a launch costs
one more Python call and one C++ boolean.
"""

from __future__ import annotations

import re
import threading
from contextlib import contextmanager

import jax
import numpy as np

__all__ = [
    "trace", "annotate", "region",
    "tracing", "launch", "note", "records", "reset_records", "hlo_scopes",
]

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


# -- program records ----------------------------------------------------------

MAX_RECORDS = 64  # keys kept; the oldest goes first

_LOCK = threading.Lock()
_NOTED: dict = {}  # key -> _Noted, in the order the keys were first seen


class _Noted:
    """One program seen in a profiler session: what :func:`records`
    needs to lower it again, and what it then read."""

    __slots__ = ("module", "fn", "lower", "args", "kw", "built")

    def __init__(self, module, fn, lower, args, kw):
        self.module, self.fn, self.lower = module, fn, lower
        self.args, self.kw, self.built = args, kw, None


def tracing() -> bool:
    """Is a profiler session open?  One C++ boolean."""
    return jax.profiler.TraceAnnotation.is_enabled()


def launch(fn, *args, **kw):
    """``fn(*args, **kw)`` for a jitted ``fn``; while a profiler session
    is open the program is noted first (:func:`note`)."""
    if tracing():
        note(fn, args, kw)
    return fn(*args, **kw)


def _abstract(x):
    """An array leaf as its ``ShapeDtypeStruct`` (the sharding of a
    committed array with it); any other leaf as it is."""
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if x.committed else None,
            weak_type=x.weak_type)
    if isinstance(x, np.ndarray):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


def _leaf_key(x):
    if isinstance(x, jax.ShapeDtypeStruct):
        return (x.shape, str(x.dtype), str(x.sharding), x.weak_type)
    try:
        hash(x)
    except TypeError:
        return repr(x)
    return x


def note(fn, args, kw, lower=None) -> None:
    """Note the program that ``fn(*args, **kw)`` is about to launch,
    once a key: its module name (``jit_`` + the function's name, every
    character that is no letter, digit or ``_`` made ``_``) and the
    call's signature, the arguments with every array leaf replaced by
    its ``ShapeDtypeStruct``.  A record holds no ``jax.Array``: what a
    noted ``fn`` closes over, or takes as a static argument, is the
    caller's to keep small (the streamed-KRR chunk programs' spec holds
    the feature maps and ``block_fn``).  A later call with the same key
    replaces the function (programs built anew every call, as the
    streamed trainer's are for a closure ``block_fn``, leave one set
    behind, the newest).  ``lower``, where given, makes the jitted
    function that :func:`records` lowers in place of ``fn``: the same
    program without the side effects of tracing ``fn``
    (``plans.SketchPlan``).  Nothing is noted under an enclosing trace:
    that call launches nothing."""
    leaves, tree = jax.tree.flatten((args, kw))
    if any(isinstance(x, jax.core.Tracer) for x in leaves):
        return
    leaves = [_abstract(x) for x in leaves]
    module = "jit_" + re.sub(r"\W", "_", getattr(fn, "__name__", "fn"))
    key = (module, tree, tuple(_leaf_key(x) for x in leaves))
    with _LOCK:
        noted = _NOTED.get(key)
        if noted is None:
            a, k = jax.tree.unflatten(tree, leaves)
            _NOTED[key] = _Noted(module, fn, lower, a, k)
            while len(_NOTED) > MAX_RECORDS:
                del _NOTED[next(iter(_NOTED))]
        elif noted.fn is not fn:
            noted.fn, noted.lower, noted.built = fn, lower, None


def reset_records() -> None:
    """Forget every noted program."""
    with _LOCK:
        _NOTED.clear()


def records() -> list:
    """A record for every program noted since :func:`reset_records`,
    oldest first: ``module``; ``signature`` (``(args, kw)``, abstract);
    ``scopes``, the map ``{instruction name: [its own op_name, then
    [opcode, op_name] of every instruction of the computation it
    calls]}`` over the compiled program's computations (a fusion's
    callee flattened into the fusion's entry, fusions nested in it too);
    and the compiler's ``argument_bytes``, ``output_bytes``,
    ``temp_bytes`` and ``alias_bytes``.  A program that cannot be
    lowered again gives ``module``, ``signature`` and ``error`` instead.

    Built here, on the first call after a program was noted, never
    inside a launch: the program is traced and lowered again and the
    compile is one ``jax.jit`` already did (its own executable, or the
    compile cache's).  Call it after the profiler session has closed:
    the lowering would otherwise stand in the trace."""
    with _LOCK:
        noted = list(_NOTED.values())
    out = []
    for n in noted:
        if n.built is None:
            n.built = _build(n)
        out.append(n.built)
    return out


def _build(n: _Noted) -> dict:
    rec = {"module": n.module, "signature": (n.args, n.kw)}
    try:
        fn = n.lower() if n.lower is not None else n.fn
        traced = fn.trace(*n.args, **n.kw)
        lowered = traced.lower()
        compiled = lowered.compile()
        text = compiled.as_text()
        if _stale(text, _source_scopes(traced.jaxpr.jaxpr)):
            compiled = _compile_again(lowered)
            text = compiled.as_text()
        rec["scopes"] = hlo_scopes(text)
        mem = compiled.memory_analysis()
        for field in ("argument", "output", "temp", "alias"):
            rec[field + "_bytes"] = int(getattr(mem, field + "_size_in_bytes"))
    except Exception as e:  # noqa: BLE001 - a record says why, a reader goes on
        rec["error"] = f"{type(e).__name__}: {e}"[:300]
        rec.pop("scopes", None)
    return rec


def _source_scopes(jaxpr) -> set:
    """The ``jax.named_scope`` names on the equations of ``jaxpr`` and of
    every jaxpr inside it."""
    out, todo = set(), [jaxpr]
    while todo:
        j = todo.pop()
        for eqn in j.eqns:
            out.update(e.name for e in eqn.source_info.name_stack.stack
                       if type(e).__name__ == "Scope")
            todo.extend(jax.core.jaxprs_in_params(eqn.params))
    return out


_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _stale(text: str, scopes: set) -> bool:
    """Does a compiled program's text lack a scope its source opens?
    The compile cache's key leaves metadata out, so an executable cached
    from a source with other scopes, and the very same instructions,
    comes back with that source's ``op_name``s."""
    names = "\n".join(set(_OP_NAME.findall(text)))
    return any(s not in names for s in scopes)


def _compile_again(lowered):
    """Compile ``lowered`` under a cache key that holds its metadata (a
    compiler option given, so that the executable ``jax.jit`` keeps is
    not handed back)."""
    flag = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        return lowered.compile(
            compiler_options={"xla_embed_ir_in_executable": False})
    finally:
        jax.config.update(flag, was)


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([^\s(]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?(\S+) = (.*)$")
_OPCODE = re.compile(r"(?:^|[ )])([a-z][a-z0-9\-]*)\(")
_CALLS = re.compile(r"calls=%?([^\s,)}]+)")


def hlo_scopes(text: str) -> dict:
    """``{instruction: [op_name, [opcode, op_name], ...]}`` from the text
    of a compiled program (:func:`records`).  Every instruction of every
    computation that is no fusion's callee has an entry; a ``fusion``'s
    holds the instructions of the computation it calls, in the text's
    order, those of the fusions nested there after their own."""
    comps: dict = {}  # computation -> [(name, opcode, op_name, callee)]
    rows = None
    for line in text.split("\n"):
        if rows is not None and (m := _INSTRUCTION.match(line)):
            name, rest = m.groups()
            op = _OPCODE.search(rest)
            meta = _OP_NAME.search(rest)
            callee = _CALLS.search(rest)
            rows.append((name, op.group(1) if op else "",
                         meta.group(1) if meta else "",
                         callee.group(1) if callee else None))
        elif m := _COMPUTATION.match(line):
            rows = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            rows = None
    fused = {callee for rows in comps.values()
             for _, op, _, callee in rows if op == "fusion" and callee}

    def inside(callee, seen=()):
        out = []
        for _, op, op_name, inner in comps.get(callee, ()):
            out.append([op, op_name])
            if op == "fusion" and inner and inner not in seen:
                out.extend(inside(inner, seen + (callee,)))
        return out

    scopes = {}
    for comp, rows in comps.items():
        if comp in fused:
            continue
        for name, op, op_name, callee in rows:
            scopes[name] = [op_name] + (
                inside(callee) if op == "fusion" and callee else [])
    return scopes
