"""100 * (1 - union of the device-busy intervals / traced window), over
the traced steps, mean over the chips."""


def read(run, params):
    return None if run.trace is None else run.trace.idle_pct()
