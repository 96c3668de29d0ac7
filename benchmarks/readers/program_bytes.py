"""The largest ``params['field']`` (``temp_bytes``: a program's
temporaries, which ``peak_bytes_in_use`` leaves out; ``argument_bytes``,
``output_bytes``, ``alias_bytes``) among the program records whose module
ran in the traced steps: the compiler's own count for the program as it
was lowered again.  None without a trace or such a record."""

import scope_reduce


def read(run, params):
    t = run.trace
    if t is None:
        return None
    ran = scope_reduce.modules_run(t.devices, t.lo, t.hi)
    sizes = [rec[params["field"]] for rec in scope_reduce.program_records()
             if rec.get("module") in ran and params["field"] in rec]
    return max(sizes) if sizes else None
