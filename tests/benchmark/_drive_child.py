"""Child process of ``test_benchmark.py``: every rehearsal of one entry
kind in one JAX start (x64 off, as the driver runs the benchmark).

For each cell file of the kind (in the manifest or not): the sound program and the control through
``control.readings`` (the control is the reference in the precision
below the configuration's, in the program's place), then the harness's
own ``main`` with the timed path broken underneath, once a fault.  One
JSON object on the last line.
"""

import contextlib
import glob
import io
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(REPO, "benchmarks"), REPO]

import control  # noqa: E402
import run as harness  # noqa: E402


def altered(entry_cls):
    """An answer altered where it is produced: one entry moved by the
    answer's own norm."""
    import jax.numpy as jnp

    class Altered(entry_cls):
        def step(self):
            rec = super().step()
            a = rec["answer"]
            rec["answer"] = a.at[(0,) * a.ndim].add(jnp.linalg.norm(a))
            return rec

    return Altered


def half_left_out(entry_cls):
    """Half of the rows left out of every step (warm-up too, so nothing
    compiles in the window), the answer taken over the rest."""

    class Half(entry_cls):
        def step(self):
            z, held = self.sizes, {}
            rows = "m" if "m" in z else "rows"
            for name in ("A", "b", "X", "Y"):
                if hasattr(self, name):
                    held[name] = getattr(self, name)
                    setattr(self, name, held[name][: z[rows] // 2])
            z[rows] //= 2
            try:
                return super().step()
            finally:
                z[rows] *= 2
                for name, value in held.items():
                    setattr(self, name, value)

    return Half


FAULTS = {"answer_altered": altered, "half_left_out": half_left_out}


def harness_line(cell, seed, break_entry):
    """The last line of ``run.py --rehearse`` with ``break_entry`` applied
    to the entry class that the harness looks up."""
    real = harness.load_module

    def load(directory, name):
        mod = real(directory, name)
        if directory == "entries":
            mod.Entry = break_entry(mod.Entry)
        return mod

    harness.load_module = load
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            rc = harness.main(["--workload", cell, "--seed", str(seed),
                               "--seconds", "0.2", "--trace", "0", "--rehearse"])
    finally:
        harness.load_module = real
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    return {"rc": rc, **{k: line[k] for k in
                         ("correct", "attempted", "failed", "compared")}}


def main(kind):
    result = {}
    for path in sorted(glob.glob(os.path.join(harness.HERE, "workloads", "*.json"))):
        name = os.path.splitext(os.path.basename(path))[0]
        _, cell, config = harness.load_cell(name)
        if cell["entry"]["kind"] != kind:
            continue
        mod = harness.load_module("entries", kind)
        entry = mod.Entry(config, cell, 7, cell["chips"], tiny=True)
        result[name] = {
            "limits": cell["limits"],
            **control.readings(entry, True),
            "sound": harness_line(name, 2**31 + 11, lambda cls: cls),
            **{f: harness_line(name, 13, brk) for f, brk in FAULTS.items()},
        }
    print(json.dumps(result))


if __name__ == "__main__":
    import jax

    jax.config.update("jax_enable_x64", False)
    main(sys.argv[1])
