"""Milliseconds a traced step in which no operation ran on the device
(gaps of the ``XLA Ops`` line inside the traced steps, mean over the
chips) *and* the host was inside a program span whose name matches
``params['span']`` (a regular expression on ``skylark:<name>``); with
``params['invert']``, inside none that matches.  None where the trace
holds no program span at all (a program from before the spans)."""

import span_reduce


def read(run, params):
    t = run.trace
    if t is None or not span_reduce.has_spans(t.host):
        return None
    ns = [span_reduce.idle_in_spans_ns(ops or mods, t.host, params["span"],
                                       t.lo, t.hi, params.get("invert", False))
          for mods, ops in t.devices.values()]
    return sum(ns) / len(ns) / 1e6 / t.n_steps
