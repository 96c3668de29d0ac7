"""Device program executions a step, from the trace's module line."""


def read(run, params):
    t = run.trace
    return None if t is None else t.launches() / t.n_steps
