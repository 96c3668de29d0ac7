"""Share of the roofline by named scope: the least time the chip could
take for the work the algorithm needs, over the device time of the
operations under a scope matching ``params['scope']`` inside the modules
matching ``params['module']`` (``scope_dev_ms``'s join, mean over the
chips).  The work comes from the entry's cost function ``params['cost']``
(flop and bytes of one step, from shapes and the steps' mean counts
``params['info']``); the least time is the larger of flop over the peak
rate of ``params['flops_peak']`` and bytes over the peak bandwidth
(``peaks.json``).  None where ``scope_dev_ms`` reads nothing or a count
is missing: a program without the scopes or the counts has no share."""

import importlib.util
import os


def _scope_dev_ms():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "scope_dev_ms.py")
    spec = importlib.util.spec_from_file_location("bench_readers_scope_dev_ms", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(run, params):
    measured_ms = _scope_dev_ms().read(run, params)
    if not measured_ms:
        return None
    info = {k: run.info_mean(k) for k in params.get("info", [])}
    if any(v is None for v in info.values()):
        return None
    flop, nbytes = run.costs[params["cost"]](run.entry.sizes, info)
    peak = run.peaks[run.device.device_kind]
    least = max(flop / peak[params["flops_peak"]], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / (measured_ms / 1e3)
