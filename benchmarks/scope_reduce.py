"""Device time by the program's own named scopes: the trace's operations
joined to the program records.

An ``XLA Ops`` event of a v5e trace is named by its HLO instruction and
carries no ``op_name``; the program that ran can say which named scope
(``jax.named_scope``) each instruction came from.  The library notes
every hot program it launches while a profiler session is open, and
``libskylark_tpu.utils.profiling.records()`` gives, for each, the map
``{instruction: [its own op_name, [opcode, op_name] of every instruction
of the computation it calls ...]}`` (``docs/observability.md``, "Program
records").  Here the two are joined on the device's own lines, so there
is no clock to reconcile: an operation belongs to the module execution it
starts in (an execution to the window its middle lies in), and to **one
scope**: a fusion that holds a ``dot`` or a
``convolution`` to that instruction's ``op_name`` (the first, where there
are several), any other operation to its own.  So a prox or an epilogue
fused into a product reads under the product's scope.

Everything works on the plain event lists ``(name, start_ns,
duration_ns)`` of ``trace_reduce`` and on plain record dicts, so
``tests/benchmark/test_scope_readers.py`` checks the arithmetic on
hand-made traces and maps.  A program without records (a parent commit)
gives no record to any module: the readers then return None and the
metric is left out.
"""

from __future__ import annotations

import bisect
import re

from trace_reduce import self_time, strip_id

MIN_FOUND = 0.99  # of an execution's time, for a record to be its program's
PRODUCTS = ("dot", "convolution")
NAME_CHARS = 79  # strip_id keeps 80 characters, the ``%`` among them


def instruction(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return strip_id(name).lstrip("%")


def owner(entry) -> str:
    """The ``op_name`` that an operation's time is put down to: that of
    the first ``dot`` or ``convolution`` it holds, else its own."""
    for opcode, op_name in entry[1:]:
        if opcode in PRODUCTS:
            return op_name
    return entry[0]


def scope_of(op_name: str, rx):
    """The outermost element of the path ``op_name`` that matches ``rx``
    (``jit(f)/while/body/admm.features/rft.epilogue.turns/mul`` is under
    ``admm.features`` for ``admm\\.``), or None."""
    return next((el for el in op_name.split("/") if rx.search(el)), None)


def maps_of(records):
    """``[(module, {instruction: entry})]`` of the records that hold a
    map, the names cut as ``strip_id`` cuts an event's."""
    return [(rec["module"], {k[:NAME_CHARS]: v for k, v in rec["scopes"].items()})
            for rec in records if "scopes" in rec]


def program_of(maps, module: str, ops):
    """The instruction map, among ``maps`` (:func:`maps_of`), of the
    record of ``module`` whose names cover the operations ``(instruction,
    ns)`` of one execution, or None where none covers ``MIN_FOUND`` of
    their time: two records of one name are two programs (``jit_run`` of
    two shapes, two plans' ``jit_traced``)."""
    total = sum(ns for _, ns in ops)
    best, share = None, 0.0
    for name, names in maps:
        if name != module or not total:
            continue
        found = sum(ns for inst, ns in ops if inst in names) / total
        if found > share:
            best, share = names, found
    return best if share >= MIN_FOUND else None


def executions(modules, ops):
    """``[(module, start_ns, duration_ns, [(instruction, self ns)])]`` of
    every module event of one device plane, the operations that start
    inside it with their self time (what runs nested in an operation, as
    a ``while``'s body does, counts for itself), and the ``(start_ns,
    self ns)`` of the operations that start inside no execution."""
    alone = sorted((s, instruction(name), ns) for name, s, ns in self_time(ops))
    starts = [s for s, _, _ in alone]
    out, inside_some = [], set()
    for name, s, d in modules:
        i, j = bisect.bisect_left(starts, s), bisect.bisect_left(starts, s + d)
        inside_some.update(range(i, j))
        out.append((strip_id(name), s, d, [(inst, ns) for _, inst, ns in alone[i:j]]))
    orphans = [(s, ns) for k, (s, _, ns) in enumerate(alone) if k not in inside_some]
    return out, orphans


def in_window(start, duration, lo, hi) -> bool:
    """An execution belongs to the window its middle lies in: the device's
    stamps stand a fraction of a millisecond off the host's, so the first
    execution of the first traced step may start before the step does."""
    return lo <= start + duration // 2 < hi


def joined(modules, ops, records, module_pattern: str, lo, hi, ran=None):
    """``(self ns, op_name or None)`` of every operation that starts
    inside an execution, of ``[lo, hi)`` (:func:`in_window`), of a module
    matching ``module_pattern``: the ``op_name`` it is put down to
    (:func:`owner`), None where the execution found no record or the
    record lacks the instruction.  ``ran``: the plane's
    :func:`executions`, where the caller has them already."""
    rx, maps = re.compile(module_pattern), maps_of(records)
    out = []
    for module, s, d, inside in ran or executions(modules, ops)[0]:
        if not (in_window(s, d, lo, hi) and rx.search(module)):
            continue
        names = program_of(maps, module, inside)
        for inst, ns in inside:
            entry = names.get(inst) if names else None
            out.append((ns, None if entry is None else owner(entry)))
    return out


def scope_ns(modules, ops, records, module_pattern, scope_pattern, lo, hi):
    """``({scope: ns}, found)`` on one device plane: the nanoseconds of
    the matching modules' operations by the scope their ``op_name`` lies
    under (the outermost path element matching ``scope_pattern``;
    operations under none are left out), and the share of those modules'
    self time that found its instruction in a record."""
    rx = re.compile(scope_pattern)
    by_scope: dict = {}
    total = found = 0
    for ns, op_name in joined(modules, ops, records, module_pattern, lo, hi):
        total += ns
        if op_name is None:
            continue
        found += ns
        scope = scope_of(op_name, rx)
        if scope is not None:
            by_scope[scope] = by_scope.get(scope, 0) + ns
    return by_scope, (found / total if total else 0.0)


def recorded_ns(modules, ops, records, lo, hi):
    """``(found, total)`` on one device plane: the self nanoseconds of
    the operations of the window (those of its executions, and those
    that start in ``[lo, hi)`` inside no execution), and of those that
    ran inside an execution of a recorded program with their instruction
    found."""
    ran, orphans = executions(modules, ops)
    rows = joined(modules, ops, records, "", lo, hi, ran)
    return (sum(ns for ns, op_name in rows if op_name is not None),
            sum(ns for ns, _ in rows) + sum(ns for s, ns in orphans if lo <= s < hi))


def modules_run(devices, lo, hi) -> set:
    """The bare names of the modules that ran in ``[lo, hi)``
    (:func:`in_window`) on any device plane."""
    return {strip_id(name) for mods, _ in devices.values()
            for name, s, d in mods if in_window(s, d, lo, hi)}


def program_records() -> list:
    """``profiling.records()`` of the program under test; ``[]`` where it
    keeps none (a commit from before the records)."""
    try:
        from libskylark_tpu.utils import profiling
    except ImportError:
        return []
    return profiling.records() if hasattr(profiling, "records") else []
