"""Device milliseconds a traced step of the module executions that the
host launched while inside a program span matching ``params['span']``
(mean over the chips).  A launch is a host event matching
``params['launch']``, the runtime's span around one enqueue; it is put
down to the span it *starts* in, wherever on the device's clock the
execution then falls.  None, not a number, when under 99 % of the
window's module time finds its launch, or where the trace holds no
program span."""

import span_reduce

MIN_FOUND = 0.99


def read(run, params):
    t = run.trace
    if t is None or not span_reduce.has_spans(t.host):
        return None
    hit = []
    for mods, _ in t.devices.values():
        ns, share = span_reduce.launched_ns(
            mods, t.host, params["span"], params["launch"], t.lo, t.hi)
        if share < MIN_FOUND:
            return None
        hit.append(ns)
    return sum(hit) / len(hit) / 1e6 / t.n_steps
