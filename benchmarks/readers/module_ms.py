"""Device milliseconds a step in the XLA modules whose name matches
``params['module']`` (a regular expression), from the trace."""


def read(run, params):
    t = run.trace
    if t is None:
        return None
    s = t.module_s(params["module"])
    return 1e3 * s / t.n_steps if s > 0 else None
