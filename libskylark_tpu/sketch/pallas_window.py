"""Pallas TPU windowed scatter-accumulate for the hash-sketch hot loop.

The streaming COLUMNWISE apply of the hash sketches (CWT/MMT/WZT —
``hash.py::_apply_slice_columnwise`` / ``apply_slice_kernel``) is a ROW
scatter-add per hash window:

    out[b[i], :] += v[i] * A[i, :]        i in [0, k)

XLA lowers this (via ``jax.ops.segment_sum``) to a TPU scatter.  TPU
has no vector scatter, but the row form needs none: one scalar-indexed
VECTOR accumulate per entry —
``scratch[b[i], :] += v[i] * a_row`` — touches all m lanes at once, so
the scalar-loop cost amortizes over the row width instead of per
element.

Layout: grid ``(Tm, Kc)`` with the entry-chunk axis Kc fastest.  Each
grid step owns a (ck, TM) tile of A in VMEM and that chunk's (nnz, ck)
bucket and value tables in SMEM (they are read a scalar at a time at a
dynamic position; VMEM serves only vector loads at 128-aligned lanes);
a persistent f32 VMEM scratch of shape (S_pad, TM) is
the accumulator for the current lane tile, zeroed at the first chunk and
emitted at the last.  The optional ``acc`` operand is folded into the
emit (``out = acc + scratch``) — a single IEEE f32 add of the same
partial the unfused composite would produce, so fusing the streaming
accumulator add changes no bits (the plan layer's planned≡eager
contract rides on exactly this).

Padding is value-preserving by construction: padded entries carry
``v = 0`` and zero A rows, so each contributes an exact ``+0.0``.
Out-of-domain counter draws (WZT's 1/Exp can be inf) must be zeroed by
the CALLER in ``v`` before the call — inf·0 would otherwise poison the
row — which the hash dispatcher already does for traced windows.

Stacked hashes: ``b``/``v`` may be (nnz, k) — the OSNAP/SJLT layout —
in which case every hash function's entries accumulate into the SAME
persistent scratch in one launch (the A tile streams through VMEM once
for all nnz hashes instead of once per hash).  The 1-D form is exactly
the nnz=1 special case of the stacked kernel, so the generated op
sequence for nnz=1 is unchanged.

The module also carries the FJLT sampled-transform epilogue
(:func:`gather_scaled_rows`): ``out[j, :] = scale · T[idx[j], :]`` — a
scalar-indexed vector COPY instead of an RMW, same sublane-dynamic
addressing, bitwise equal to the XLA ``scale * T[idx, :]`` gather (pure
selection + the same elementwise multiply in the same dtype).

Routing: anything the static gates below exclude keeps the XLA path;
``SKYLARK_NO_PALLAS=1`` forces it.  On a TPU a shape the gates admit
compiles or raises — nothing probes and falls back.
``tests/test_tpu_compile.py`` compiles both kernels for a described v5e
chip at the bench shapes; ``chip_smoke.py`` runs :func:`self_check` and
:func:`self_check_gather` on the chip.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

__all__ = [
    "scatter_rows",
    "supported",
    "worthwhile",
    "self_check",
    "gather_scaled_rows",
    "supported_gather",
    "worthwhile_gather",
    "self_check_gather",
]

# Entries per grid step along the chunk axis.  Larger chunks cut
# grid-step overhead at the cost of the (ck, TM) A-tile VMEM; the
# effective chunk shrinks to the (128-aligned) entry count for small
# windows so tests and thin streams don't pay 8x padding.
_CK = int(os.environ.get("SKYLARK_WINDOW_CHUNK", "1024"))
# Lane-tile width of the accumulator (and of each A tile).
_TM = 512
# Scratch accumulator budget: S_pad * TM f32 elements (4 MB at 1<<20 —
# out + acc blocks ride alongside it, keeping total VMEM well under the
# ~16 MB arena).
_VMEM_ELEMS = 1 << 20
# Entry count past which HBM staging of the padded copies stops paying.
_MAX_K = 150_000_000
# Default-on threshold: below this many entries the launch overhead of
# the scalar-loop kernel is not worth it over XLA's scatter.
_MIN_K = int(os.environ.get("SKYLARK_WINDOW_MIN_K", "4096"))
# Default-on threshold for the sampled-epilogue gather: below this many
# sampled rows XLA's gather is already launch-bound cheap.
_MIN_GATHER = int(os.environ.get("SKYLARK_WINDOW_MIN_GATHER", "512"))


def _ceil_to(x: int, q: int) -> int:
    return -(-x // q) * q


def _tiles(k: int, num_segments: int, m: int):
    """(ck, Kc, TM, Tm, S_pad) for a (k, m) block into num_segments rows."""
    ck = min(_ceil_to(_CK, 128), _ceil_to(k, 128))
    Kc = -(-k // ck)
    TM = min(_TM, _ceil_to(m, 128))
    Tm = -(-m // TM)
    S_pad = _ceil_to(num_segments, 8)
    return ck, Kc, TM, Tm, S_pad


def supported(k: int, num_segments: int, m: int, nnz: int = 1) -> bool:
    """Hard feasibility of the window kernel for a (k, m) block with
    ``nnz`` stacked hash functions — shape and VMEM only.  Forced modes
    (``SKYLARK_PALLAS_WINDOW=1|interpret``) honor this gate but not
    :func:`worthwhile`."""
    if os.environ.get("SKYLARK_NO_PALLAS", "0") == "1":
        return False
    if k < 1 or num_segments < 1 or m < 1 or nnz < 1:
        return False
    if nnz * k > _MAX_K:
        return False
    _, _, TM, _, S_pad = _tiles(k, num_segments, m)
    return S_pad * TM <= _VMEM_ELEMS


def worthwhile(k: int, num_segments: int, m: int, nnz: int = 1) -> bool:
    """Amortization gate for the TPU-DEFAULT route (forced modes skip
    it): enough entries to pay the launch + scalar-loop setup."""
    return nnz * k >= _MIN_K


def _window_kernel(with_acc: bool, *refs):
    from jax.experimental import pallas as pl

    if with_acc:
        b_ref, v_ref, a_ref, acc_ref, out_ref, sc_ref = refs
    else:
        b_ref, v_ref, a_ref, out_ref, sc_ref = refs
        acc_ref = None
    kc = pl.program_id(1)

    @pl.when(kc == 0)
    def _zero():
        sc_ref[:, :] = jnp.zeros_like(sc_ref)

    nnz, ck = b_ref.shape

    # Rows of A are read one aligned sublane group at a time (8 rows of
    # f32, 16 of bf16 — a packed dtype has no dynamic single-row load)
    # and split by STATIC slices; ck is a multiple of 128, so groups
    # never straddle the tile.
    group = 8 * (4 // a_ref.dtype.itemsize)

    def entries(g, c):
        # One scalar-indexed VECTOR accumulate per (hash, entry): the
        # bucket and value are SMEM scalars, the accumulator row is a
        # dynamic SUBLANE address (pl.ds on the second-minor axis), and
        # the full TM-lane row rides the VPU.  Entries stay in ascending
        # order, so the f32 sum order is the row order.  The hash axis
        # is a static unroll — each A row feeds all nnz accumulates.
        i0 = pl.multiple_of(g * jnp.int32(group), group)
        rows = a_ref[pl.ds(i0, group), :].astype(jnp.float32)
        for j in range(group):
            row = rows[j:j + 1, :]
            for h in range(nnz):
                r = b_ref[h, i0 + j]
                sc_ref[pl.ds(r, 1), :] = (
                    sc_ref[pl.ds(r, 1), :] + v_ref[h, i0 + j] * row
                )
        return c

    jax.lax.fori_loop(0, ck // group, entries, 0)

    @pl.when(kc == pl.num_programs(1) - 1)
    def _emit():
        if acc_ref is not None:
            out_ref[:, :] = acc_ref[:, :] + sc_ref[:, :]
        else:
            out_ref[:, :] = sc_ref[:, :]


@partial(jax.jit, static_argnames=("num_segments", "interpret", "with_acc"))
def _scatter_rows_impl(A, b, v, acc, num_segments, interpret, with_acc):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k, m = A.shape
    nnz = b.shape[0]
    ck, Kc, TM, Tm, S_pad = _tiles(k, num_segments, m)
    if A.dtype not in (jnp.float32, jnp.bfloat16):
        # f32-accumulate boundary cast (f64 arrives only via callers
        # that accepted the demotion — core.precision.f32_accumulable).
        A = A.astype(jnp.float32)
    kp, mp = Kc * ck - k, Tm * TM - m
    A_p = jnp.pad(A, ((0, kp), (0, mp)))
    # Per-chunk scalar tables: (Kc, nnz, ck) with the chunk axis squeezed
    # out of the block, so each grid step sees one whole (nnz, ck) table.
    # They live in SMEM — the kernel reads them one scalar at a time at a
    # dynamic position, which VMEM (vector loads, 128-aligned lanes)
    # cannot serve.
    b_p = (
        jnp.pad(b.astype(jnp.int32), ((0, 0), (0, kp)))
        .reshape(nnz, Kc, ck).transpose(1, 0, 2)
    )
    v_p = (
        jnp.pad(v.astype(jnp.float32), ((0, 0), (0, kp)))
        .reshape(nnz, Kc, ck).transpose(1, 0, 2)
    )

    in_specs = [
        pl.BlockSpec((None, nnz, ck), lambda tm, kc: (kc, 0, 0),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((None, nnz, ck), lambda tm, kc: (kc, 0, 0),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((ck, TM), lambda tm, kc: (kc, tm),
                     memory_space=pltpu.VMEM),
    ]
    operands = [b_p, v_p, A_p]
    if with_acc:
        acc_p = jnp.pad(acc, ((0, S_pad - num_segments), (0, mp)))
        in_specs.append(
            pl.BlockSpec((S_pad, TM), lambda tm, kc: (0, tm),
                         memory_space=pltpu.VMEM)
        )
        operands.append(acc_p)

    out = pl.pallas_call(
        partial(_window_kernel, with_acc),
        grid=(Tm, Kc),  # Kc fastest: scratch persists across chunks
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (S_pad, TM), lambda tm, kc: (0, tm), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((S_pad, Tm * TM), jnp.float32),
        scratch_shapes=[pltpu.VMEM((S_pad, TM), jnp.float32)],
        interpret=interpret,
    )(*operands)

    return out[:num_segments, :m]


def scatter_rows(A, b, v, num_segments: int, *, acc=None, interpret=False):
    """``out[t, :] = sum_{i: b[i]==t} v[i] * A[i, :]`` (f32), optionally
    ``+ acc`` folded into the kernel's emit.  ``A`` is (k, m) f32/bf16
    (other floats boundary-cast to f32), ``b`` int32 in
    [0, num_segments), ``v`` f32 with any out-of-domain entries already
    zeroed by the caller.  ``acc``, when given, must be (num_segments,
    m) f32 — the fused result is bitwise equal to ``acc + scatter_rows(
    ...)`` (one IEEE add of the same partial).  ``b``/``v`` may also be
    stacked (nnz, k) — every hash row scatters into the same output in
    one launch.  Caller gates with :func:`supported`."""
    if acc is not None and acc.dtype != jnp.float32:
        raise TypeError(
            f"fused acc must be float32, got {acc.dtype}; the unfused "
            "composite handles other accumulator dtypes"
        )
    if b.ndim == 1:
        b, v = b[None, :], v[None, :]
    if b.shape != v.shape:
        raise ValueError(f"b/v shape mismatch: {b.shape} vs {v.shape}")
    return _scatter_rows_impl(
        A, b, v, acc if acc is not None else jnp.zeros((), jnp.float32),
        num_segments, interpret, acc is not None,
    )


def self_check(
    k: int = 16384, num_segments: int = 1000, m: int = 320,
    interpret: bool = False, nnz: int = 1,
) -> float:
    """Max *relative* error of the window kernel vs the XLA reference on
    random buckets/values — the ONE validator shared by
    ``chip_smoke.py`` and the hardware guard (``tests/_hw_guards.py``),
    so the two cannot drift.  The off-tile
    shape (S=1000, m=320) exercises every padding seam.  ``nnz > 1``
    validates the stacked-hash layout.  Raises on lowering failure;
    callers decide the tolerance (1e-5 is the established hardware
    bar)."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    shape = (k,) if nnz == 1 else (nnz, k)
    b = jax.random.randint(k1, shape, 0, num_segments, dtype=jnp.int32)
    v = jax.random.normal(k2, shape, jnp.float32)
    A = jax.random.normal(k3, (k, m), jnp.float32)
    out = scatter_rows(A, b, v, num_segments, interpret=interpret)
    ref = jax.ops.segment_sum(
        (v.reshape(nnz, k)[:, :, None] * A[None, :, :]).reshape(-1, m),
        b.reshape(-1), num_segments=num_segments,
    )
    jax.block_until_ready((out, ref))
    scale = jnp.maximum(jnp.max(jnp.abs(ref)), 1e-30)
    return float(jnp.max(jnp.abs(out - ref)) / scale)


# ---------------------------------------------------------------------------
# FJLT sampled-transform epilogue: scaled row gather.
# ---------------------------------------------------------------------------


def _gather_tiles(nrows: int, s: int, m: int):
    """(cs, Sc, TM, Tm, R_pad) for sampling s rows of a (nrows, m) T."""
    cs = min(_ceil_to(1024, 128), _ceil_to(s, 128))
    Sc = -(-s // cs)
    TM = min(_TM, _ceil_to(m, 128))
    Tm = -(-m // TM)
    R_pad = _ceil_to(nrows, 8)
    return cs, Sc, TM, Tm, R_pad


def supported_gather(nrows: int, s: int, m: int) -> bool:
    """Hard feasibility of the gather kernel: the full (R_pad, TM)
    source tile must fit the VMEM budget alongside the (cs, TM) out."""
    if os.environ.get("SKYLARK_NO_PALLAS", "0") == "1":
        return False
    if nrows < 1 or s < 1 or m < 1 or s > _MAX_K:
        return False
    _, _, TM, _, R_pad = _gather_tiles(nrows, s, m)
    return R_pad * TM <= _VMEM_ELEMS


def worthwhile_gather(nrows: int, s: int, m: int) -> bool:
    """Amortization gate for the TPU-DEFAULT route: enough sampled rows
    to beat XLA's already-cheap gather."""
    return s >= _MIN_GATHER


def _gather_kernel(idx_ref, t_ref, scale_ref, out_ref):
    from jax.experimental import pallas as pl

    (cs,) = idx_ref.shape
    scale = scale_ref[0, 0]

    def entry(i, c):
        # Scalar-indexed vector COPY: pure selection plus the same
        # elementwise multiply XLA's ``scale * T[idx, :]`` performs, in
        # the same dtype — bitwise equal to the gather composite.
        r = idx_ref[i]
        out_ref[pl.ds(i, 1), :] = t_ref[pl.ds(r, 1), :] * scale
        return c

    jax.lax.fori_loop(0, cs, entry, 0)


@partial(jax.jit, static_argnames=("interpret",))
def _gather_rows_impl(T, idx, scale, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nrows, m = T.shape
    (s,) = idx.shape
    cs, Sc, TM, Tm, R_pad = _gather_tiles(nrows, s, m)
    sp, mp = Sc * cs - s, Tm * TM - m
    T_p = jnp.pad(T, ((0, R_pad - nrows), (0, mp)))
    # Padded indices select row 0 of T; those rows are cropped below.
    # SMEM table, one whole (cs,) chunk per grid step (the kernel reads
    # it a scalar at a time at a dynamic position).
    idx_p = jnp.pad(idx.astype(jnp.int32), (0, sp)).reshape(Sc, 1, cs)
    scale_arr = jnp.asarray(scale, T.dtype).reshape(1, 1)

    out = pl.pallas_call(
        _gather_kernel,
        grid=(Tm, Sc),
        in_specs=[
            pl.BlockSpec((None, None, cs), lambda tm, sc: (sc, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((R_pad, TM), lambda tm, sc: (0, tm),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda tm, sc: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(
            (cs, TM), lambda tm, sc: (sc, tm), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((Sc * cs, Tm * TM), T.dtype),
        interpret=interpret,
    )(idx_p, T_p, scale_arr)

    return out[:s, :m]


def gather_scaled_rows(T, idx, scale, *, interpret=False):
    """``out[j, :] = scale * T[idx[j], :]`` — the FJLT sampled-transform
    epilogue as one scalar-indexed vector-copy kernel.  ``T`` is
    (nrows, m) float, ``idx`` int in [0, nrows), ``scale`` a python
    float / 0-d array cast to ``T.dtype``.  Bitwise equal to the XLA
    composite ``scale * T[idx, :]`` (selection plus the identical
    elementwise multiply).  Caller gates with
    :func:`supported_gather`."""
    return _gather_rows_impl(T, idx, scale, interpret)


def self_check_gather(
    nrows: int = 3000, s: int = 4096, m: int = 320,
    interpret: bool = False,
) -> float:
    """Max relative error of the gather kernel vs ``scale * T[idx, :]``
    on a padding-seam shape.  Expected 0.0 exactly (pure selection +
    identical multiply); raises on lowering failure."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(11))
    T = jax.random.normal(k1, (nrows, m), jnp.float32)
    idx = jax.random.randint(k2, (s,), 0, nrows, dtype=jnp.int32)
    scale = 0.3125
    out = gather_scaled_rows(T, idx, scale, interpret=interpret)
    ref = jnp.asarray(scale, T.dtype) * T[idx, :]
    jax.block_until_ready((out, ref))
    scale_r = jnp.maximum(jnp.max(jnp.abs(ref)), 1e-30)
    return float(jnp.max(jnp.abs(out - ref)) / scale_r)
