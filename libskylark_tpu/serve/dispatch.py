"""Device-parallel dispatch: one coalesced batch, every local chip.

The serve executors (``batcher.py``) run one fused program per batch on
whatever device the worker thread is pinned to.  For batches whose
padded rung clears a flop gate, this module reroutes the heavy half of
the executor — the sketch apply for LS-solve, the feature-map /
Gram-matrix block for predict — through a ``shard_map`` program over the
batch axis, so a single dispatch uses every local device instead of one
(the serving answer to the reference's one-engine-many-clients ``capi/``
surface).  The light half (the (s, kb) triangular solve, the Z·W
coefficient matmul) stays on the worker's device, UNCHANGED from the
single-device path — which is what makes the parity argument short.

Schedules (both communication-free — no psum ever reorders a sum):

- LS-solve shards the RHS **column** (batch) axis through
  ``parallel.collectives.batch_sharded_program``: each shard applies the
  FULL sketch to its column block (contrast ``columnwise_sharded``,
  which splits the contraction and merges with a psum — approximate by
  construction).  Widths keep the batcher's lane-uniform sub-ladder:
  ``d | kb`` AND ``(kb / d) % 8 == 0``.
- Predict shards the **row** (request) axis — the
  ``rowwise_sharded`` schedule — under the same width gate.

Bit-parity contract — VERIFIED, not assumed.  Per-slot purity makes
each output slot depend only on its own input slot, but XLA's CPU
kernels (gemm micro-kernel tiling, pocketfft batch vectorization) pick
accumulation schedules BY OPERAND WIDTH, so a kb-wide program and d
(kb/d)-wide programs agree bitwise only for some (transform, geometry,
dtype) combinations — measured, not derivable.  So the first dispatch
of every (anchor, rung, d, dtype) program is a **parity probe**: it
runs the sharded program AND the caller's single-device reference on
the live batch, compares bits, and caches the verdict.  A matching
program serves sharded from then on; a mismatch tombstones the program
and the executor keeps its single-device path.  Either way the caller
returns single-device bits on the probe call — sharded dispatch is
bitwise-identical to single-device dispatch by construction.

Gates, in the ``sketch/pallas_window.py`` idiom:

- :func:`supported`: hard feasibility (device count divides the rung,
  lane-uniform shard width).  Honored even when forced.
- :func:`worthwhile`: amortization — enough flops in the heavy half to
  pay the cross-device staging.  ``SKYLARK_SERVE_SHARD=1`` forces the
  route past this gate (tests, benchmarks); ``=0`` disables it
  entirely (bit-for-bit the PR-10 executor, probes and all); unset =
  auto.  ``SKYLARK_SERVE_SHARD_MIN_FLOPS`` overrides the threshold.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import telemetry
from ..parallel.collectives import batch_sharded_program
from ..sketch.base import Dimension

__all__ = [
    "supported",
    "worthwhile",
    "shard_devices",
    "maybe_sketch_sharded",
    "maybe_feature_sharded",
    "maybe_kernel_sharded",
    "clear_cache",
]

# Default amortization floor: below ~3e7 flops in the heavy half, the
# per-shard dispatch + resharding overhead eats the win on every
# backend we measured.  Env-overridable for hardware with a different
# crossover (and mooted by SKYLARK_SERVE_SHARD=1 in tests/benches).
_MIN_FLOPS = 3e7

_AXIS = "serve_batch"

# (id(anchor), kind, kb, d, dtype) -> [anchor, program, verdict].  The
# anchor (sketch / model) is kept strongly referenced so the id key can
# never be recycled under us; the population is bounded by the registry
# census × rung ladder × device splits — the same budget Server.prime
# compiles.  verdict: None = unprobed, True = parity held (serve
# sharded), False = tombstoned (single-device forever).
_PROGRAMS: dict = {}


def clear_cache() -> None:
    _PROGRAMS.clear()


def supported(kb: int, d: int) -> bool:
    """Can a kb-wide rung split over d devices without leaving the
    lane-uniform sub-ladder (shard width a multiple of the base rung)?"""
    return d >= 2 and kb % d == 0 and (kb // d) % 8 == 0


def worthwhile(flops: float) -> bool:
    """Amortization gate for the AUTO route (forced mode skips it)."""
    floor = _MIN_FLOPS
    env = os.environ.get("SKYLARK_SERVE_SHARD_MIN_FLOPS")
    if env:
        try:
            floor = float(env)
        except ValueError:
            pass
    return flops >= floor


def shard_devices(kb: int, flops: float):
    """The device list a kb-wide dispatch may shard over, or ``None``.

    Largest feasible split wins (every chip busy beats a tidy factor);
    ``None`` whenever the gates say the single-device path should run.
    """
    mode = os.environ.get("SKYLARK_SERVE_SHARD", "")
    if mode == "0":
        return None
    if mode != "1" and not worthwhile(flops):
        return None
    devs = jax.local_devices()
    for d in range(len(devs), 1, -1):
        if supported(kb, d):
            return devs[:d]
    return None


def _dispatch_sharded(anchor, kind, kb, devs, dtype, build, x, spec,
                      reference, rows, entries):
    """Shared probe-then-serve core.  Returns the result the caller
    must use, or ``None`` (tombstoned / never feasible) meaning "run
    your single-device path yourself"."""
    key = (id(anchor), kind, kb, len(devs), str(dtype))
    slot = _PROGRAMS.get(key)
    if slot is None:
        mesh = Mesh(np.array(devs), (_AXIS,))
        slot = [anchor, jax.jit(build(mesh)), None]
        _PROGRAMS[key] = slot
    _, prog, verdict = slot
    if verdict is False:
        return None
    # Explicit reshard first: the worker thread may hand us an array
    # committed to its pinned device, which a jitted shard_map would
    # reject as an incompatible-devices error instead of moving.
    mesh = Mesh(np.array(devs), (_AXIS,))
    xs = jax.device_put(x, NamedSharding(mesh, spec))
    out = prog(xs)
    if verdict is None:
        ref = reference()
        a = np.asarray(out)
        b = np.asarray(ref)
        if rows is not None:  # padding rows are garbage on both routes
            a, b = a[:rows], b[:rows]
        match = bool(np.array_equal(a, b))
        slot[2] = match
        telemetry.inc(
            "serve.sharded_verified" if match else "serve.sharded_rejected"
        )
        telemetry.event(
            "serve", "sharded_probe",
            {"kind": kind, "bucket": kb, "devices": len(devs),
             "match": match},
        )
        for e in entries or ():
            e.trace["events"].append(
                {"kind": "sharded_probe", "op": kind,
                 "devices": len(devs), "match": match}
            )
        if not match:
            return None
        # Parity held: the sharded bits ARE the reference bits; hand
        # back the reference object so the probe call is free of doubt.
        return ref
    telemetry.inc("serve.sharded_dispatch")
    for e in entries or ():
        e.trace["events"].append(
            {"kind": "sharded", "op": kind, "devices": len(devs)}
        )
    return out


def maybe_sketch_sharded(S, B, kb: int, entries=None, reference=None):
    """S·B with B's kb columns (the coalesced RHS batch) sharded over
    local devices; ``None`` when the gates (or a failed parity probe)
    say stay single-device.  ``B`` is the (m, kb) padded block, already
    dtype-cast; ``reference`` computes the single-device S·B for the
    probe."""
    m = B.shape[0]
    devs = shard_devices(kb, 2.0 * m * S.s * kb)
    if devs is None:
        return None

    def build(mesh):
        def local(b):
            return S.apply(b, Dimension.COLUMNWISE)

        return batch_sharded_program(local, mesh)

    return _dispatch_sharded(
        S, "ls", kb, devs, B.dtype, build, B, P(None, _AXIS),
        reference, None, entries,
    )


def maybe_feature_sharded(model, Xp, true_rows: int, entries=None,
                          reference=None):
    """The feature-map block Z of a predict batch, rows (requests)
    sharded; ``None`` when gated off or tombstoned.  Mirrors the
    planned ``_feature_map_predict`` math; the probe compares true rows
    only (padding rows are zeroed on the planned route, garbage here —
    both die at the caller's slice)."""
    maps = getattr(model, "maps", None)
    if not maps:
        return None
    kb, d_in = Xp.shape
    flops = 2.0 * kb * d_in * sum(s.s for s in maps)
    devs = shard_devices(kb, flops)
    if devs is None:
        return None

    def build(mesh):
        axes = tuple(mesh.axis_names)

        def local(x):
            blocks = []
            for s in maps:
                Z = s.apply(x, Dimension.ROWWISE)
                if model.scale_maps:
                    Z = Z * jnp.asarray(
                        np.sqrt(Z.shape[-1] / d_in), Z.dtype
                    )
                blocks.append(Z)
            return jnp.concatenate(blocks, axis=-1)

        return jax.shard_map(
            local, mesh=mesh, in_specs=P(axes, None),
            out_specs=P(axes, None), check_vma=False,
        )

    return _dispatch_sharded(
        model, "predict", kb, devs, Xp.dtype, build, jnp.asarray(Xp),
        P(_AXIS, None), reference, true_rows, entries,
    )


def maybe_kernel_sharded(model, Xp, true_rows: int, entries=None,
                         reference=None):
    """Gram-matrix predict with query rows sharded; ``None`` when gated
    off or tombstoned.  Returns the full padded (kb, t) output — the
    caller slices true rows."""
    if not hasattr(model, "kernel"):
        return None
    kb, d_in = Xp.shape
    n_train = model.X_train.shape[0]
    devs = shard_devices(kb, 2.0 * kb * n_train * d_in)
    if devs is None:
        return None

    def build(mesh):
        axes = tuple(mesh.axis_names)

        def local(x):
            return model.kernel.gram(x, model.X_train) @ model.A

        return jax.shard_map(
            local, mesh=mesh, in_specs=P(axes, None),
            out_specs=P(axes, None), check_vma=False,
        )

    return _dispatch_sharded(
        model, "kernel", kb, devs, Xp.dtype, build, jnp.asarray(Xp),
        P(_AXIS, None), reference, true_rows, entries,
    )
