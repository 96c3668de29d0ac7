"""Device milliseconds a traced step in the operations of the ``XLA Ops``
line whose name matches ``params['op']`` (a regular expression on the
bare name, ``%all-reduce.3``): their self time (what runs nested in one,
as a ``while``'s body does, is taken out), inside the traced steps, mean
over the chips.  None where no chip's line holds such an operation: a
program on one chip has no collective, and that is nothing to read, not 0."""

import re

from trace_reduce import clip, self_time, strip_id


def read(run, params):
    t = run.trace
    if t is None:
        return None
    rx = re.compile(params["op"])
    ns = []
    for _, ops in t.devices.values():
        hit = [(name, s, d) for name, s, d in self_time(ops)
               if rx.search(strip_id(name))]
        ns.append(sum(e - s for s, e in clip(hit, t.lo, t.hi)))
    return sum(ns) / len(ns) / 1e6 / t.n_steps if any(ns) else None
