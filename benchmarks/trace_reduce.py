"""From a profiler trace to numbers: device-busy union, idle gaps and who
the host was in them, device time per XLA module and per operation.

Everything works on plain event lists ``(name, start_ns, duration_ns)``
so that ``tests/benchmark`` can check the arithmetic on a hand-made
trace; :func:`load` is the only part that touches the ``.xplane.pb``
(through ``jax.profiler.ProfileData``, nothing but JAX).

What a v5e trace holds (looked at by hand, PERF.md section 3): one plane
``/device:TPU:<i>`` per chip with the lines ``XLA Modules`` (one event a
program execution, named ``jit_<function>(<fingerprint>)``) and
``XLA Ops`` (one event an HLO operation); the plane ``/host:CPU`` with
one line a thread, holding the ``TraceAnnotation`` spans this benchmark
puts around every step and JAX's own host spans.
"""

from __future__ import annotations

import glob
import os
import re

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


def merge(intervals):
    """Sorted, disjoint ``[start, end]`` covering the same instants."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(events, lo, hi):
    """``(start, end)`` of the events' parts inside ``[lo, hi]``."""
    return [(max(s, lo), min(s + d, hi)) for _, s, d in events
            if s + d > lo and s < hi]


def busy_ns(events, lo, hi) -> int:
    return sum(e - s for s, e in merge(clip(events, lo, hi)))


def gaps(events, lo, hi):
    """The idle intervals of ``[lo, hi]``, longest first."""
    out, at = [], lo
    for s, e in merge(clip(events, lo, hi)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return sorted(out, key=lambda g: g[0] - g[1])


def strip_id(name: str) -> str:
    """``jit_run(123456)`` -> ``jit_run`` and ``%fusion.3 = f32[...] ...``
    -> ``%fusion.3``: the fingerprint and the operands change with every
    edit of the program, the function's or operation's name does not."""
    return re.sub(r"\(\d+\)$", "", name.split(" = ")[0])[:80]


def self_time(events):
    """The events with the time of the events nested in them taken out
    (a ``while`` holds its body's operations): ``(name, start, self_ns)``."""
    out, stack = [], []  # stack of [name, start, end, self]
    for name, s, d in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and s >= stack[-1][2]:
            top_ = stack.pop()
            out.append((top_[0], top_[1], top_[3]))
        if stack:
            stack[-1][3] -= min(d, stack[-1][2] - s)
        stack.append([name, s, s + d, d])
    out.extend((n, s, t) for n, s, _, t in stack)
    return out


def module_ns(modules, pattern: str, lo, hi) -> int:
    """Device nanoseconds inside ``[lo, hi]`` of the module executions
    whose name matches ``pattern`` (``re.search`` on the bare name)."""
    rx = re.compile(pattern)
    hit = [ev for ev in modules if rx.search(strip_id(ev[0]))]
    return sum(e - s for s, e in clip(hit, lo, hi))


def top(events, lo, hi, k: int):
    """``[name, seconds]`` of the k names with most time in ``[lo, hi]``."""
    total: dict = {}
    for name, s, d in events:
        part = min(s + d, hi) - max(s, lo)
        if part > 0:
            total[strip_id(name)] = total.get(strip_id(name), 0) + part
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t / 1e9] for n, t in rows]


def attribute(gap, steps, host):
    """What the host was doing in an idle gap: the benchmark's step it
    fell in (``between steps`` otherwise) and the innermost host span,
    other than the step's own, that covers the gap's middle."""
    mid = (gap[0] + gap[1]) // 2
    step = next((n for n, s, d in steps if s <= mid < s + d), "between steps")
    inner = None
    for name, s, d in host:
        if s <= mid < s + d and (inner is None or d < inner[1]):
            inner = (name, d)
    return step if inner is None else f"{step}: {inner[0]}"


class Trace:
    """The traced steps of one run.  ``devices`` maps a device plane to
    its ``(modules, ops)`` event lists; ``steps`` are the benchmark's
    step annotations and ``host`` every other host span."""

    def __init__(self, devices: dict, steps: list, host: list):
        self.devices, self.steps, self.host = devices, steps, host
        self.lo = min(s for _, s, _ in steps)
        self.hi = max(s + d for _, s, d in steps)
        self.window_s = (self.hi - self.lo) / 1e9
        busy = [busy_ns(ops or mods, self.lo, self.hi)
                for mods, ops in devices.values()]
        self.busy_s = sum(busy) / len(busy) / 1e9

    @property
    def n_steps(self) -> int:
        return len(self.steps)

    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def module_s(self, pattern: str) -> float:
        """Device seconds in the matching modules, mean over the chips."""
        ns = [module_ns(mods, pattern, self.lo, self.hi)
              for mods, _ in self.devices.values()]
        return sum(ns) / len(ns) / 1e9

    def launches(self) -> float:
        """Program executions started inside the traced steps, a chip."""
        n = [sum(self.lo <= s < self.hi for _, s, _ in mods)
             for mods, _ in self.devices.values()]
        return sum(n) / len(n)

    def breakdown(self) -> dict:
        mods, ops = next(iter(self.devices.values()))
        ev = ops or mods
        return {
            "device_ops": top(self_time(ev), self.lo, self.hi, 10),
            "idle_gaps": [
                [attribute(g, self.steps, self.host), (g[1] - g[0]) / 1e9]
                for g in gaps(ev, self.lo, self.hi)[:5]
            ],
            "device_modules": top(mods, self.lo, self.hi, 10),
        }


def newest_profile(trace_dir: str):
    """ProfileData of the newest ``.xplane.pb`` under ``trace_dir``, or None."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return ProfileData.from_file(paths[-1]) if paths else None


def load(trace_dir: str, step_prefix: str):
    """The :class:`Trace` of the newest profile under ``trace_dir``, or
    None where it holds no device plane or no step."""
    data = newest_profile(trace_dir)
    if data is None:
        return None
    devices, steps, host = {}, [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                               for ev in ln.events] for ln in plane.lines}
            if lines.get(MODULE_LINE):
                devices[plane.name] = (lines[MODULE_LINE], lines.get(OP_LINE, []))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    row = (ev.name, int(ev.start_ns), int(ev.duration_ns))
                    (steps if ev.name.startswith(step_prefix) else host).append(row)
    if not devices or not steps:
        return None
    return Trace(devices, steps, host)


def describe(trace_dir: str, k: int = 12) -> None:
    """Print what a trace holds: planes, lines, event counts and the
    names with most time on each line.  For looking at one by hand."""
    for plane in newest_profile(trace_dir).planes:
        print(f"plane {plane.name!r}")
        for ln in plane.lines:
            ev = [(e.name, int(e.start_ns), int(e.duration_ns)) for e in ln.events]
            if not ev:
                continue
            lo, hi = min(s for _, s, _ in ev), max(s + d for _, s, d in ev)
            print(f"  line {ln.name!r}: {len(ev)} events, "
                  f"{lo / 1e9:.6f}..{hi / 1e9:.6f} s")
            for name, secs in top(ev, lo, hi, k):
                print(f"    {secs:10.6f} s  {name[:100]}")


if __name__ == "__main__":
    import sys

    describe(sys.argv[1])
