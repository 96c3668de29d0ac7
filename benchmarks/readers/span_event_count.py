"""Host events a traced step whose name matches ``params['event']`` (a
regular expression) and that start inside a program span matching
``params['span']``; anywhere in the traced steps when ``span`` is null.
With a ``span`` given, None where the trace holds no program span at all
(a program from before the spans): nothing was looked for, not 0 found."""

import span_reduce


def read(run, params):
    t = run.trace
    if t is None or (params["span"] is not None
                     and not span_reduce.has_spans(t.host)):
        return None
    n = span_reduce.count_events(t.host, params["event"], params["span"],
                                 t.lo, t.hi)
    return n / t.n_steps
