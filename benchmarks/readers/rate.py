"""Units (``params['unit']``) that the window's completed steps produced,
over the window's seconds."""


def read(run, params):
    done = run.units(params["unit"])
    return done / run.window_s if done else None
