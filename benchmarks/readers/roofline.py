"""Share of the roofline: the least time the chip could take for the
work the algorithm needs, over the device time measured in the modules
matching ``params['module']``.  The work comes from the entry's cost
function ``params['cost']`` (flop and bytes of one step, from shapes and
the steps' mean counts); the least time is the larger of flop over the
peak rate of ``params['flops_peak']`` and bytes over the peak bandwidth
(``peaks.json``, by ``device_kind``: an unknown kind is an error)."""


def read(run, params):
    t = run.trace
    if t is None:
        return None
    measured = t.module_s(params["module"]) / t.n_steps
    if measured <= 0:
        return None
    info = {k: run.info_mean(k) for k in params.get("info", [])}
    if any(v is None for v in info.values()):
        return None
    flop, nbytes = run.costs[params["cost"]](run.entry.sizes, info)
    peak = run.peaks[run.device.device_kind]
    least = max(flop / peak[params["flops_peak"]], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / measured
