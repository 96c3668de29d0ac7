#!/usr/bin/env python3
"""The readings a cell's limits are set from, in one process.

    python3 benchmarks/control.py --workload <name> --seeds 1,2,3 [--controls 3]

For every seed: the data, one step of the program as the window drives
it, the plain reference, and the number(s) ``correct`` compares: the
lower reading.  For the first ``--controls`` seeds also the control: the
reference computed in the precision below the configuration's and put in
the program's place, which has to come out as not correct: the upper
reading.  One JSON line a seed; the benchmark's own runs never run this.
Needs the TPU unless ``--rehearse`` is given.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as harness  # noqa: E402 - the harness's own look-up by name


def readings(entry, control: bool) -> dict:
    entry.setup()
    rec = entry.step()
    entry.release()
    out = {"info": rec["info"], "bad": rec["bad"],
           "program": {n: v for n, v, _ in entry.check([rec["answer"]])}}
    if control:
        out["control"] = {n: v for n, v, _ in entry.check([entry.control()])}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    _, cell, config = harness.load_cell(args.workload)
    sys.path.insert(0, harness.ROOT)

    import jax

    jax.config.update("jax_enable_x64", False)
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("control.py: no TPU", file=sys.stderr)
        return 2
    from libskylark_tpu.utils import compile_cache

    compile_cache.place()
    kind = harness.load_module("entries", cell["entry"]["kind"])
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        entry = kind.Entry(config, cell, seed, cell["chips"], tiny=args.rehearse)
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "limits": cell["limits"],
                          **readings(entry, i < args.controls)}), flush=True)
        del entry
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
