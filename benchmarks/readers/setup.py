"""Seconds from the start of the process to the start of the window:
imports, data made from the seed, warm-up and, in a run that compiles,
compilation."""


def read(run, params):
    return run.setup_s
