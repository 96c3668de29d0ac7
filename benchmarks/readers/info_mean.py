"""Mean over the window's completed steps of a count the entry read from
the program's ``info`` (``params['key']``)."""


def read(run, params):
    return run.info_mean(params["key"])
