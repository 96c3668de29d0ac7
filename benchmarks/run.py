#!/usr/bin/env python3
"""One cell of the benchmark, once, in one process.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its entry kind and its metrics are found by
name from ``BENCHMARK.json`` and the data files beside this one; nothing
here lists them (``benchmarks/README.md``).  The run makes its data on
the device from ``--seed``, warms the entry up, drives it in a closed
loop for ``--seconds``, frees the program's state, compares every answer
of the window with the entry's plain reference, and prints one JSON
object as the last line of standard output.  It exits 2 without a TPU.

``--rehearse`` (never given by the driver) runs the same control flow at
the configuration's ``rehearsal`` sizes wherever JAX puts it, says
``"rehearsal": true`` on its last line and prints no time, rate or
share: only counts.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # set-up is counted from here

import argparse
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WARMUP_STEPS = 2  # the first compiles; the second shows that nothing is left to
TRACE_STEPS = 3   # steps of the window that a --trace 1 run records


def load_json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def load_module(directory: str, name: str):
    """``benchmarks/<directory>/<name>.py``, found by name."""
    path = os.path.join(HERE, directory, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{directory}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def named(rows, name):
    for cell in rows:
        if cell["name"] == name:
            return cell
    raise KeyError(name)


def load_cell(name: str):
    """(manifest, cell, configuration) of the cell ``name``.  The cell's
    own file repeats its manifest entry (the tests hold the two together),
    so a cell that is not, or not yet, in the manifest runs too."""
    manifest = load_json(ROOT, "BENCHMARK.json")
    cell = load_json(HERE, "workloads", name + ".json")
    config = load_json(ROOT, named(manifest["configs"], cell["config"])["file"])
    return manifest, cell, config


def cell_metrics(manifest, group: str, cell: str):
    """The manifest's metrics of ``group`` that this cell reports: those
    that list it under ``workloads`` and, of those with no such key,
    every end-to-end metric and every per-layer metric whose ``moves``
    this cell reports."""
    def reports(m):
        if "workloads" in m:
            return cell in m["workloads"]
        return "moves" not in m or any(
            m["moves"] == e["name"] for e in cell_metrics(manifest, "end_to_end", cell))

    return [m for m in manifest[group] if reports(m)]


class CompileCounter:
    """JAX's own compile events (copied from ``chip_smoke.py``): every
    jit-cache miss that reaches the backend is a ``request``; a ``hit``
    was served by the persistent cache, so ``requests - hits`` programs
    were really compiled."""

    def __init__(self, jax):
        self.requests = self.hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def compiled(self) -> int:
        return self.requests - self.hits


class Run:
    """What the readers read: the window, its steps and, in a traced
    run, the reduced trace and the phase timer."""

    def __init__(self, **kw):
        self.setup_s = self.window_s = 0.0
        self.steps: list = []       # one record per completed step
        self.trace = None           # trace_reduce.Trace of the traced steps
        self.timer = None           # utils.PhaseTimer handed to the entry
        self.__dict__.update(kw)

    def units(self, key: str) -> float:
        return sum(s["units"].get(key, 0) for s in self.steps)

    def info_mean(self, key: str):
        vals = [s["info"][key] for s in self.steps if key in s["info"]]
        return sum(vals) / len(vals) if vals else None


def step_seconds(records):
    """[min, median, max] seconds of the window's steps: a far-off run
    shows here whether one step or every step was slow."""
    ends = sorted(r["t_end"] for r in records)
    took = sorted(b - a for a, b in zip([0.0] + ends, ends))
    return [took[0], took[len(took) // 2], took[-1]] if took else None


def finite(answer) -> bool:
    import jax
    import numpy as np

    return all(np.isfinite(np.asarray(leaf, np.float32)).all()
               for leaf in jax.tree.leaves(answer))


def drive(entry, seconds: float, counter, plans, trace_dir=None):
    """The closed loop.  Returns (completed steps, failed steps, window
    seconds).  A step that raises, compiles, or that the entry calls bad
    is failed; its answer is not compared and its work not counted."""
    import jax

    steps, failed = [], []
    traced = trace_dir is not None
    if traced:
        jax.profiler.start_trace(trace_dir)
    t_start = time.perf_counter()
    i = 0
    while True:
        c0, p0 = counter.compiled(), plans.stats()["compiles"]
        try:
            with jax.profiler.TraceAnnotation(f"bench_step_{i}"):
                rec = entry.step()
        except Exception as e:  # noqa: BLE001 - a failed step is counted, the run goes on
            rec = {"bad": f"raised {type(e).__name__}: {e}"[:300]}
        compiled = counter.compiled() - c0 + plans.stats()["compiles"] - p0
        if compiled and not rec.get("bad"):
            rec["bad"] = f"compiled {compiled} programs inside the window"
        rec["t_end"] = time.perf_counter() - t_start
        (failed if rec.get("bad") else steps).append(rec)
        i += 1
        if traced and i == TRACE_STEPS:
            jax.profiler.stop_trace()
            traced = False
        if time.perf_counter() - t_start >= seconds:
            break
    window_s = time.perf_counter() - t_start
    if traced:
        jax.profiler.stop_trace()
    return steps, failed, window_s


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="copy the raw trace of a --trace 1 run here, to look at")
    args = ap.parse_args(argv)

    manifest, cell, config = load_cell(args.workload)

    # The program is measured as shipped: none of its switches reaches it.
    for key in [k for k in os.environ if k.startswith("SKYLARK_")]:
        del os.environ[key]
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)

    import jax

    jax.config.update("jax_enable_x64", False)
    devices = jax.devices()
    if not args.rehearse and (
        devices[0].platform != "tpu" or len(devices) < cell["chips"]
    ):
        print(f"run.py: cell {cell['name']} needs {cell['chips']} TPU chip(s), "
              f"JAX found {len(devices)} x {devices[0].platform}",
              file=sys.stderr)
        return 2

    from libskylark_tpu import plans
    from libskylark_tpu.utils import PhaseTimer, compile_cache

    import trace_reduce

    compile_cache.place()
    counter = CompileCounter(jax)
    entry_kind = load_module("entries", cell["entry"]["kind"])
    entry = entry_kind.Entry(
        config, cell, args.seed, cell["chips"], tiny=args.rehearse
    )
    entry.setup()
    for _ in range(WARMUP_STEPS):
        entry.step()
    warm_compiled = counter.compiled()

    run = Run(entry=entry, costs=entry_kind.COSTS,
              peaks=load_json(HERE, "peaks.json"), device=devices[0])
    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        run.timer = entry.timer = PhaseTimer()
    run.setup_s = time.perf_counter() - _T0
    try:
        run.steps, failed, run.window_s = drive(
            entry, args.seconds, counter, plans, trace_dir
        )
        window_compiled = counter.compiled() - warm_compiled
        if trace_dir:
            run.trace = trace_reduce.load(trace_dir, "bench_step_")
            if args.keep_trace:
                shutil.copytree(trace_dir, args.keep_trace, dirs_exist_ok=True)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    used = devices[: cell["chips"]]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)

    # Only now the reference: the program's peak is read and its state freed.
    answers = []
    for rec in list(run.steps):
        answer = rec.pop("answer")
        if finite(answer):
            answers.append(answer)
        else:
            rec["bad"] = "non-finite answer"
            run.steps.remove(rec)
            failed.append(rec)
    entry.release()
    t_check = time.perf_counter()
    compared = entry.check(answers) if answers else []
    check_s = time.perf_counter() - t_check
    correct = bool(compared) and all(v <= lim for _, v, lim in compared)

    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(manifest, group, cell["name"]):
        spec = load_json(HERE, "layer_metrics" if args.trace else "end_to_end",
                         m["name"] + ".json")
        if args.rehearse and m["source"] != "program_counter":
            continue  # a CPU run gives counts, never a device number
        value = load_module("readers", spec["reader"]["kind"]).read(
            run, spec["reader"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    line = {"correct": correct, "attempted": len(run.steps) + len(failed),
            "failed": len(failed), "metrics": metrics, "device": device}
    if run.trace is not None and not args.rehearse:
        device["busy_s"], device["window_s"] = run.trace.busy_s, run.trace.window_s
        line["breakdown"] = run.trace.breakdown()
    if args.rehearse:
        line["rehearsal"] = True
    line["diagnostics"] = {
        "setup_s": run.setup_s, "window_s": run.window_s, "check_s": check_s,
        "compile_requests": counter.requests, "cache_hits": counter.hits,
        "compiled_in_warmup": warm_compiled,
        "compiled_in_window": window_compiled,
        "compile_seconds": counter.seconds, "plans": plans.stats(),
        "failed_reasons": sorted({f["bad"] for f in failed})[:5],
        "step_s_min_median_max": step_seconds(run.steps + failed),
        "step_info": run.steps[-1]["info"] if run.steps else None,
    }
    # last, and in plain numbers: NaN and inf are no JSON, so they print as null
    line["compared"] = {name: [value if math.isfinite(value) else None, limit]
                        for name, value, limit in compared}
    sys.stdout.flush()
    for name, value, limit in compared:
        print(f"compared {name}: {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
