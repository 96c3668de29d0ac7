"""Window seconds over the units (``params['unit']``: solutions, rows)
that the window's completed steps produced: all the time over all the
work, never a median of steps."""


def read(run, params):
    done = run.units(params["unit"])
    return run.window_s / done if done else None
