"""Fused Pallas RFUT kernel: one HBM pass for D-multiply + WHT.

The XLA lowering of the Kronecker WHT makes several full HBM round-trips
(mul, per-factor contraction, scale) — at FJLT's shapes the transform is
bandwidth-bound, so passes are everything.  This kernel performs

    out = H_NB · (D ⊙ pad(x))      (orthonormal, per row)

in a single read + single write per (TM, NB) VMEM tile, using the
mixed-product factorization ``H_NB = (H_f1 ⊗ I_128) · (I_f1 ⊗ H_128)``:

1. the ``I ⊗ H_128`` half is a contract-last ``dot_general`` against a
   dense ±1 H_128 on the MXU (128 = native lane width, the one reshape
   Mosaic supports);
2. the ``H_f1 ⊗ I`` half is a decimation butterfly on *contiguous* lane
   halves — ``H_{2k}⊗I x = [H_k⊗I (a+b); H_k⊗I (a−b)]`` — pure VPU
   add/sub on static slices, no transposes, and it leaves the output in
   natural Sylvester order (bit-compatible with :func:`fut.wht`).

Used automatically by RFUT/FJLT on TPU when shapes qualify (2-D input,
transform on the last axis, 256 ≤ NB ≤ 2^15, rows divisible by a tile
size); everything else falls back to the XLA path.  CPU tests run the
kernel in ``interpret=True`` mode.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .fut import _hadamard

__all__ = [
    "rfut_rowwise",
    "rfut_rowwise_sampled",
    "supported",
    "supported_sampled",
]

_F2 = 256  # minor factor (lane-multiple; 256² H keeps the MXU busy)
_TILE_CANDIDATES = (512, 256, 128, 64, 32, 16, 8)


def _tile_rows(m: int, nb: int, dtype=jnp.float32) -> int | None:
    """Largest tile that divides m and keeps the kernel's (tm, nb)
    working buffers inside Mosaic's 16 MB scoped-VMEM limit."""
    # The butterfly keeps ~log2(f1) live (tm, nb) f32 intermediates on
    # the Mosaic stack; 2 MB per buffer fits for bf16 inputs (tm=128 at
    # nb=4096).  f32 inputs carry the HIGHEST-precision contraction's
    # operand splits on top — at 2 MB the chip's compiler refuses the
    # kernel ("Scoped allocation with size 17.29M and limit 16.00M
    # exceeded scoped vmem limit") — so they get half the rows.
    per_buffer = (2 << 20) if jnp.dtype(dtype) == jnp.bfloat16 else (1 << 20)
    budget = per_buffer // (nb * 4)
    for t in _TILE_CANDIDATES:
        if t <= max(budget, 8) and m % t == 0:
            return t
    return None


def supported(m: int, n: int, nb: int) -> bool:
    k = nb.bit_length() - 1
    if nb != (1 << k) or nb < 2 * _F2 or nb > (1 << 15):
        return False
    return _tile_rows(m, nb) is not None


def _butterfly_kron_eye(x, f1: int):
    """(H_f1 ⊗ I_w)·x over the lane axis of x (tm, f1·w), natural order."""
    parts = [x]
    level = f1
    while level > 1:
        nxt = []
        for blk in parts:
            half = blk.shape[1] // 2
            a = blk[:, :half]
            b = blk[:, half:]
            nxt.append(a + b)
            nxt.append(a - b)
        parts = nxt
        level //= 2
    return jnp.concatenate(parts, axis=1)


def supported_sampled(m: int, n: int, nb: int, s: int) -> bool:
    """Gate for the sampled-epilogue variant: the base kernel's gate
    plus a lane-aligned sample count (the (tm, S) output block) and a
    VMEM budget that carries the extra selected block."""
    if s < 128 or s % 128:
        return False
    if not supported(m, n, nb):
        return False
    tm = _tile_rows(m, nb)
    return tm is not None and tm * (nb + s) * 4 * 4 < (12 << 20)


def _sampled_epilogue(z, idx_row):
    """Select the S sample lanes of z (tm, nb) → (tm, S).

    ``idx_row`` is a (1, S) int32 VMEM block (pallas_call rejects
    captured constant arrays, so the host-static samples arrive as an
    input), making this a lane gather.  The v5e compiler does not lower
    it: as written, "Shape mismatch in input, indices and output"; as a
    same-shape ``take_along_axis``, Mosaic's "Not implemented: Multiple
    source vregs along gather dimension" (NB ≥ 512 spans ≥ 4 vregs).
    So no default route reaches this kernel (``fjlt._apply_pallas``);
    interpret mode keeps its numerics tested."""
    return jnp.take(z, idx_row[0], axis=1)


def _dwht_tile(nb, n, x_ref, d_ref, h2_ref):
    """Shared transform body of both kernels: D-multiply → zero-pad →
    (I⊗H_F2) MXU contraction → (H_f1⊗I) butterfly.  Returns the f32
    (tm, nb) un-normalized WHT tile."""
    tm = x_ref.shape[0]
    f1 = nb // _F2
    xdtype = x_ref.dtype
    x = x_ref[:] * d_ref[:]
    if n < nb:
        x = jnp.concatenate([x, jnp.zeros((tm, nb - n), xdtype)], axis=1)
    # (I_f1 ⊗ H_F2): contract the minor factor on the MXU.  bf16 operands
    # are exact here (H is ±1; products are just sign flips) and run the
    # MXU at full rate; accumulation is f32 via preferred_element_type.
    x3 = x.reshape(tm, f1, _F2)
    h = h2_ref[:].astype(xdtype) if xdtype == jnp.bfloat16 else h2_ref[:]
    # f32 inputs pin full precision: the MXU default truncates f32
    # operands to bf16 mantissas (silent ~1e-2 abs error on hardware —
    # caught by tests/test_pallas_hw.py; H is ±1 so only the input
    # mantissa matters).  bf16 inputs are exact already.
    y = jax.lax.dot_general(
        x3.astype(h.dtype), h,
        (((2,), (0,)), ((), ())),
        precision=None if xdtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).reshape(tm, nb)
    # (H_f1 ⊗ I_F2): contiguous-halves butterfly on the VPU, f32.
    return _butterfly_kron_eye(y, f1)


def _kernel_sampled(nb, n, s, x_ref, d_ref, h2_ref, i_ref, o_ref):
    """The fused FJLT kernel: D-multiply → WHT → STATIC sample selection
    → rescale, writing only (tm, S) to HBM.  Saves the full (m, NB)
    round-trip (write + re-read + gather) of the two-step path — the
    f32 large-S floor was bandwidth in exactly that round-trip
    (VERDICT r4 item 5; reference: ``sketch/FJLT_Elemental.hpp:144-186``
    applies the same sample-and-rescale after its local FUT)."""
    z = _dwht_tile(nb, n, x_ref, d_ref, h2_ref)
    sel = _sampled_epilogue(z, i_ref[:])
    # 1/√NB (orthonormal WHT) × √(NB/S) (sample rescale) = 1/√S.
    o_ref[:] = (sel * jnp.float32(1.0 / np.sqrt(s))).astype(o_ref.dtype)


def rfut_rowwise_sampled(x, diag, nb: int, idx, interpret: bool = False):
    """out (m, S) = FJLT(x) rowwise in ONE HBM pass: read x, write only
    the S sampled, rescaled WHT lanes.  ``idx`` must be a host/static
    integer array (the UST samples — counter-derived constants)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    idx = np.asarray(idx, np.int32)
    s = int(idx.shape[0])
    m, n = x.shape
    tm = _tile_rows(m, nb, x.dtype)
    if tm is None:
        raise ValueError(
            f"shape unsupported; check supported_sampled: no VMEM-fitting "
            f"row tile divides m={m} at nb={nb}"
        )
    dtype = x.dtype
    H2 = jnp.asarray(_hadamard(_F2.bit_length() - 1), jnp.float32)
    d2 = diag.astype(dtype).reshape(1, n)

    return pl.pallas_call(
        partial(_kernel_sampled, nb, n, s),
        grid=(m // tm,),
        in_specs=[
            pl.BlockSpec((tm, n), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((_F2, _F2), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, s), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (tm, s), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((m, s), dtype),
        interpret=interpret,
    )(x, d2, H2, jnp.asarray(idx).reshape(1, s))


def _kernel(nb, n, x_ref, d_ref, h2_ref, o_ref):
    z = _dwht_tile(nb, n, x_ref, d_ref, h2_ref)
    o_ref[:] = (z * jnp.float32(1.0 / np.sqrt(nb))).astype(o_ref.dtype)


def rfut_rowwise(x, diag, nb: int, interpret: bool = False):
    """out (m, NB) = orthonormal-WHT(pad(x ⊙ diag)) rowwise, natural
    Sylvester order (bit-compatible with the XLA ``wht``).

    ``x`` (m, n) float; ``diag`` (n,).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, n = x.shape
    tm = _tile_rows(m, nb, x.dtype)
    if tm is None:
        raise ValueError(
            f"shape unsupported; check supported: no VMEM-fitting row "
            f"tile divides m={m} at nb={nb}"
        )
    dtype = x.dtype
    H2 = jnp.asarray(_hadamard(_F2.bit_length() - 1), jnp.float32)
    d2 = diag.astype(dtype).reshape(1, n)

    return pl.pallas_call(
        partial(_kernel, nb, n),
        grid=(m // tm,),
        in_specs=[
            pl.BlockSpec((tm, n), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((_F2, _F2), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (tm, nb), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((m, nb), dtype),
        interpret=interpret,
    )(x, d2, H2)
