"""Pallas TPU segment-sum (scatter-add) for the sparse hash sketches.

XLA's TPU scatter lowering runs ~28 M nnz/s (BASELINE.md round 3) — an
order of magnitude off the HBM roofline for the CWT/SJLT BCOO
``dense_output`` path (``hash.py::_apply_sparse_dense_out``), whose work
is one flat ``out[key[i]] += val[i]`` over 1e7-1e8 entries into up to
1e8 slots (≙ the queue-then-finalize CSC build of
``hash_transform_local_sparse.hpp:88-152`` / the mixed sparse→dense
apply of ``hash_transform_Mixed.hpp``).

TPU has no vector scatter, so the kernel restructures the problem around
what the hardware does have:

1. **partition pass** (grid over entry chunks): each chunk of C entries
   is sorted by destination PARTITION (``key // V``, V = slot span per
   partition).  The rank/offset arithmetic is pure VPU work (one-hot +
   cumsum); the final in-chunk permutation is a C-trip scalar loop in
   VMEM.  The sorted chunk and its per-partition histogram row go back
   to HBM.  Padding entries get the tail partition and are never read
   again.
2. **accumulate pass** (grid (P, K), K fastest): partition p owns slot
   range [p·V, (p+1)·V) as an f32 VMEM scratch accumulator shaped
   (V/128, 128) — lane-tiled, so no 8× sublane padding.  For each chunk
   it walks the chunk's p-span (contiguous after pass 1; bounds come in
   as (1, 1) blocks of the span table) with a scalar accumulate loop —
   every entry is touched exactly ONCE across the whole grid — and at
   the last chunk writes the accumulator to its output block.

Total scalar work is 2 touches/entry (pass-1 permutation + pass-2
accumulate); everything else is vector/DMA.

STATUS: the v5e compiler refuses this kernel as written — "the last two
dimensions of your block shape [must be] divisible by 8 and 128" for the
(1, C) chunk blocks, and behind those the scalar loads and stores at
dynamic LANE positions of VMEM refs (``sk_ref[0, d] = ...``), which
Mosaic cannot prove aligned.  It is therefore on NO default route
(``hash._segment_sum`` takes ``jax.ops.segment_sum`` unless
``SKYLARK_PALLAS_SCATTER=1|interpret`` forces the kernel); interpret
mode keeps its numerics tested, and ``tests/test_tpu_compile.py`` pins
the refusal.  Restating it the way ``pallas_window`` was (scalar tables
in SMEM) is open work.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

__all__ = ["segment_sum_flat", "supported", "self_check"]

# Entries per chunk (pass-1 grid step).  Larger C cuts pass-2 grid-step
# count and chunk-revisit overhead at the cost of pass-1 VMEM (the
# (C, P+1) one-hot/cumsum pair); env-tunable so the hardware probe can
# sweep it (experiments/scatter_probe.py).
_C = int(os.environ.get("SKYLARK_SCATTER_CHUNK", "2048"))
_P = 64  # target partition count; V = ceil(T / P) rounded to 1024
_VMEM_SLOTS = 2_097_152  # max V: an 8 MB f32 accumulator


def _plan(nnz: int, num_segments: int):
    V = -(-num_segments // _P)
    V = max(-(-V // 1024) * 1024, 1024)  # (V/128, 128) stays sublane-tiled
    P = -(-num_segments // V)
    K = -(-nnz // _C)
    return K, P, V


# The two passes hold (keys, vals) plus their sorted copies in HBM
# (~16 B/entry beyond the caller's input); past this entry count the
# working set crowds a 16 GB chip and the XLA path (in-place scatter)
# is the safer choice (SJLT nnz=4 at 1e8 input nonzeros = 4e8 entries).
_MAX_NNZ = 150_000_000


def supported(nnz: int, num_segments: int) -> bool:
    if os.environ.get("SKYLARK_NO_PALLAS", "0") == "1":
        return False
    if nnz < 4 * _C or num_segments < 1024:
        return False  # too small to amortize two passes
    if nnz > _MAX_NNZ:
        return False
    _, P, V = _plan(nnz, num_segments)
    return V <= _VMEM_SLOTS and (P + 1) * V < (1 << 31)


# ---------------------------------------------------------------------------
# pass 1: chunk-sort by partition
# ---------------------------------------------------------------------------


def _cumsum_sublanes(x):
    """Inclusive cumsum along axis 0 via log-step shifted adds — static
    slices + pads only (Mosaic has no native cumulative-sum lowering;
    jnp.cumsum inside a TPU kernel is not guaranteed to lower)."""
    n, s = x.shape[0], 1
    while s < n:
        x = x + jnp.pad(x[:-s], ((s, 0), (0, 0)))
        s *= 2
    return x


def _excl_cumsum_lanes(row):
    """Exclusive cumsum along axis 1 of a (1, n) row, same log-step
    construction (lane-axis shifts are static slices)."""
    n, s = row.shape[1], 1
    out = row
    while s < n:
        out = out + jnp.pad(out[:, :-s], ((0, 0), (s, 0)))
        s *= 2
    return out - row


def _partition_kernel(
    V, PP, keys_ref, vals_ref, sk_ref, sv_ref, cnt_ref, dest_ref
):
    """Sort one (1, C) chunk by partition id; emit its histogram row."""
    C = keys_ref.shape[1]
    keys = keys_ref[0, :]
    pid = jnp.minimum(keys // V, PP - 1)  # padding keys -> tail partition
    iota_p = jax.lax.broadcasted_iota(jnp.int32, (C, PP), 1)
    onehot = (pid[:, None] == iota_p).astype(jnp.int32)
    # dtype pinned: under x64 (interpret-mode CPU tests) jnp.sum would
    # promote int32 to int64, which the int32 refs reject.
    counts_row = jnp.sum(
        onehot, axis=0, keepdims=True, dtype=jnp.int32
    )  # (1, PP)
    cnt_ref[0, :] = counts_row[0, :]
    # exclusive start of each partition's span within the sorted chunk,
    # plus each entry's rank among same-pid entries before it
    pstart_row = _excl_cumsum_lanes(counts_row)  # (1, PP)
    inc = _cumsum_sublanes(onehot)  # (C, PP)
    rank = jnp.sum(onehot * inc, axis=1, dtype=jnp.int32) - 1  # (C,)
    dest_ref[0, :] = (
        jnp.sum(onehot * pstart_row, axis=1, dtype=jnp.int32) + rank
    )

    def body(i, c):
        d = dest_ref[0, i]
        sk_ref[0, d] = keys_ref[0, i]
        sv_ref[0, d] = vals_ref[0, i]
        return c

    jax.lax.fori_loop(0, C, body, 0)


# ---------------------------------------------------------------------------
# pass 2: per-partition scalar accumulate
# ---------------------------------------------------------------------------


def _accumulate_kernel(
    V, lanemask, base_ref, sk_ref, sv_ref, start_ref, stop_ref, out_ref,
    acc_ref
):
    from jax.experimental import pallas as pl

    k = pl.program_id(1)
    K = pl.num_programs(1)

    @pl.when(k == 0)
    def _zero():
        acc_ref[:, :] = jnp.zeros_like(acc_ref)

    base = base_ref[0, 0]
    s = start_ref[0, 0]
    e = stop_ref[0, 0]

    if lanemask:
        # Lane-masked RMW: dynamic sublane index + full-lane vector ops
        # only (no dynamic LANE addressing, which Mosaic may not lower
        # for scalar stores) — ~4 vector ops per entry.
        lane_iota = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)

        def entry(i, c):
            local = sk_ref[0, i] - base
            row, lane = local // 128, local % 128
            acc_row = acc_ref[pl.ds(row, 1), :]
            acc_ref[pl.ds(row, 1), :] = acc_row + jnp.where(
                lane_iota == lane, sv_ref[0, i], jnp.float32(0)
            )
            return c

    else:

        def entry(i, c):
            local = sk_ref[0, i] - base
            row, lane = local // 128, local % 128
            acc_ref[row, lane] = acc_ref[row, lane] + sv_ref[0, i]
            return c

    jax.lax.fori_loop(s, e, entry, 0)

    @pl.when(k == K - 1)
    def _emit():
        out_ref[:, :] = acc_ref[:, :]


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def self_check(
    nnz: int = 40_000, num_segments: int = 1 << 17, interpret: bool = False
) -> float:
    """Max *relative* error of the kernel vs ``jax.ops.segment_sum`` on
    random keys/values (interpret mode on the CPU; compiled, it raises
    the chip compiler's refusal — see the module docstring).  Callers
    decide the tolerance (1e-5 is the established hardware bar)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    keys = jax.random.randint(k1, (nnz,), 0, num_segments, dtype=jnp.int32)
    vals = jax.random.normal(k2, (nnz,), jnp.float32)
    out = segment_sum_flat(vals, keys, num_segments, interpret=interpret)
    ref = jax.ops.segment_sum(vals, keys, num_segments=num_segments)
    jax.block_until_ready((out, ref))
    scale = jnp.maximum(jnp.max(jnp.abs(ref)), 1e-30)
    return float(jnp.max(jnp.abs(out - ref)) / scale)


def segment_sum_flat(vals, keys, num_segments: int, interpret: bool = False):
    """``out[t] = sum(vals[keys == t])`` for flat int32 keys in
    [0, num_segments).  Caller gates with :func:`supported`; ``vals``
    and ``keys`` are 1-D and equal length.

    Non-f32 floating ``vals`` (bf16/f16/f64) take the f32-accumulate
    boundary cast: exact on the way in for the narrow types, one
    rounding on the way out — so the precision ladders
    (``core/precision.py``) no longer force the XLA scatter lowering.
    Callers gate the f64 demotion through
    ``precision.f32_accumulable(demote_f64=...)``."""
    # Accumulate mode: "scalar" (1 scalar RMW/entry — needs dynamic-lane
    # addressing) or "lanemask" (vector RMW, no dynamic lanes).  Read
    # OUTSIDE the jitted impl so a mode switch is a fresh trace, not a
    # stale cache hit.
    lanemask = os.environ.get("SKYLARK_SCATTER_ACCUM", "scalar") == "lanemask"
    out_dtype = vals.dtype
    out = _segment_sum_impl(vals, keys, num_segments, interpret, lanemask)
    if out_dtype != jnp.float32 and jnp.issubdtype(out_dtype, jnp.floating):
        return out.astype(out_dtype)
    return out


@partial(
    jax.jit, static_argnames=("num_segments", "interpret", "lanemask")
)
def _segment_sum_impl(
    vals, keys, num_segments: int, interpret: bool, lanemask: bool
):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nnz = vals.shape[0]
    K, P, V = _plan(nnz, num_segments)
    PP = P + 1  # + tail partition for padding entries
    pad = K * _C - nnz
    keys_p = jnp.pad(
        keys.astype(jnp.int32), (0, pad), constant_values=PP * V - 1
    ).reshape(K, _C)
    vals_p = jnp.pad(vals.astype(jnp.float32), (0, pad)).reshape(K, _C)

    sk, sv, counts = pl.pallas_call(
        partial(_partition_kernel, V, PP),
        grid=(K,),
        in_specs=[
            pl.BlockSpec((1, _C), lambda k: (k, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _C), lambda k: (k, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, _C), lambda k: (k, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _C), lambda k: (k, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, PP), lambda k: (k, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K, _C), jnp.int32),
            jax.ShapeDtypeStruct((K, _C), jnp.float32),
            jax.ShapeDtypeStruct((K, PP), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((1, _C), jnp.int32)],
        interpret=interpret,
    )(keys_p, vals_p)

    # span bounds per (chunk, partition): prefix sums along PP (XLA side)
    stops = jnp.cumsum(counts, axis=1)
    starts = stops - counts
    bases = (jnp.arange(P, dtype=jnp.int32) * V).reshape(P, 1)

    out = pl.pallas_call(
        partial(_accumulate_kernel, V, lanemask),
        grid=(P, K),  # K fastest: accumulator persists across chunks
        in_specs=[
            pl.BlockSpec((1, 1), lambda p, k: (p, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _C), lambda p, k: (k, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _C), lambda p, k: (k, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda p, k: (k, p),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda p, k: (k, p),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (V // 128, 128), lambda p, k: (p, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((P * V // 128, 128), jnp.float32),
        scratch_shapes=[pltpu.VMEM((V // 128, 128), jnp.float32)],
        interpret=interpret,
    )(bases, sk, sv, starts, stops)

    return out.reshape(-1)[:num_segments]
