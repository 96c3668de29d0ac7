"""The share (%) of the traced steps' device time (self time of the
``XLA Ops`` line, mean over the chips) that ran inside executions of
recorded programs with its instruction found in the record
(``scope_reduce``).  What is missing from 100 is device work the
library launches piecemeal, op by op, or through a program that is not
called through ``profiling.launch``.  None without a trace or a record
that holds a map."""

import scope_reduce


def read(run, params):
    t = run.trace
    records = scope_reduce.program_records() if t is not None else []
    if not any("scopes" in rec for rec in records):
        return None
    shares = []
    for mods, ops in t.devices.values():
        found, total = scope_reduce.recorded_ns(mods, ops, records, t.lo, t.hi)
        if total:
            shares.append(100.0 * found / total)
    return sum(shares) / len(shares) if shares else None
