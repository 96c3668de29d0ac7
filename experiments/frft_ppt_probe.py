"""Round-3 probe: Fastfood + PPT TPU cost, current paths vs matmul re-designs.

Run on the real chip.  Not part of the package — measurement scratch that
informs sketch/frft.py + sketch/ppt.py design (VERDICT r2 item 1).

Findings (v5e, m=131072 n=4096):
- streaming Fastfood (two XLA WHTs + permutation gather):
  s=2048: 33.95 ms bf16 / 65.14 ms f32;  s=4096: 37.98 / 66.76
- realized-W prototype (host-built W): bf16 22.84 ms, A-bf16 x W-split2
  26.70 ms, A-split3 x W-split2 (5-pass) 72.00 ms -> 4-pass chosen
- a host-built W closed over by the jit is a 64 MB constant of the
  executable at s=4096 -> the package builds W IN-GRAPH from the
  counter stream
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from libskylark_tpu.core.context import SketchContext
from libskylark_tpu.sketch.frft import FastGaussianRFT
from libskylark_tpu.sketch.ppt import PPT


def _timed(fn, *args) -> float:
    t0 = time.perf_counter()
    np.asarray(fn(*args))
    return time.perf_counter() - t0


def rep_diff(build, A, r1=2, r2=6, rounds=8) -> float:
    f1, f2 = build(r1), build(r2)
    _timed(f1, A), _timed(f2, A)
    t1s, t2s = [], []
    for _ in range(rounds):
        t1s.append(_timed(f1, A))
        t2s.append(_timed(f2, A))
    t1, t2 = min(t1s), min(t2s)
    if t2 <= t1:
        return float("nan")
    return (t2 - t1) / (r2 - r1)


def frft_package(m, n, s, dtype):
    """Times whatever path the package selects (realized gemm on TPU)."""

    def build(reps):
        ctx = SketchContext(seed=7)
        sketches = [FastGaussianRFT(n, s, ctx, sigma=2.0) for _ in range(reps)]

        def run(A):
            acc = jnp.zeros((), jnp.float32)
            for S in sketches:
                acc += jnp.sum(jnp.abs(S.apply(A, "rowwise").astype(jnp.float32)))
            return acc

        return jax.jit(run)

    A = jax.random.normal(jax.random.PRNGKey(1), (m, n), dtype=dtype)
    return rep_diff(build, A)


def ppt_current(m, n, s, q, dtype, r1=1, r2=3):
    def build(reps):
        ctx = SketchContext(seed=9)
        sketches = [PPT(n, s, ctx, q=q) for _ in range(reps)]

        def run(A):
            acc = jnp.zeros((), jnp.float32)
            for S in sketches:
                acc += jnp.sum(jnp.abs(S.apply(A, "rowwise").astype(jnp.float32)))
            return acc

        return jax.jit(run)

    A = jax.random.normal(jax.random.PRNGKey(3), (m, n), dtype=dtype)
    return rep_diff(build, A, r1=r1, r2=r2, rounds=6)


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    m, n = 131_072, 4096

    if which in ("all", "frft"):
        for s in (2048, 4096):
            for dt, name in ((jnp.bfloat16, "bf16"), (jnp.float32, "f32")):
                t = frft_package(m, n, s, dt)
                print(f"FRFT package m={m} n={n} s={s} {name}: {t*1e3:.2f} ms",
                      flush=True)

    if which in ("all", "ppt"):
        for dt, name in ((jnp.float32, "f32"), (jnp.bfloat16, "bf16")):
            t = ppt_current(m, n, 1024, 3, dt)
            print(f"PPT current m={m} n={n} s=1024 q=3 {name}: {t*1e3:.2f} ms",
                  flush=True)


if __name__ == "__main__":
    main()
