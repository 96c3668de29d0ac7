"""Milliseconds a call of one phase of the ``utils.PhaseTimer`` that the
traced run hands the entry (``params['phase']``): a host clock that
blocks on the phase's result."""


def read(run, params):
    t = run.timer
    if t is None or not t.counts.get(params["phase"]):
        return None
    return 1e3 * t.totals[params["phase"]] / t.counts[params["phase"]]
