"""Device milliseconds a traced step of the operations that lie under a
named scope matching ``params['scope']`` inside the modules matching
``params['module']`` (regular expressions; ``scope_reduce`` says which
scope an operation belongs to), mean over the chips.  None, not a
number, without a trace or a program record, where under 99 % of the
matched modules' self time on some chip found its instruction in a
record, or where no operation lies under the scope."""

import scope_reduce


def read(run, params):
    t = run.trace
    records = scope_reduce.program_records() if t is not None else []
    if not records:
        return None
    hit = []
    for mods, ops in t.devices.values():
        by_scope, found = scope_reduce.scope_ns(
            mods, ops, records, params["module"], params["scope"], t.lo, t.hi)
        if found < scope_reduce.MIN_FOUND:
            return None
        hit.append(sum(by_scope.values()))
    return sum(hit) / len(hit) / 1e6 / t.n_steps if any(hit) else None
