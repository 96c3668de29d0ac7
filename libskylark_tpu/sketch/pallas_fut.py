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

__all__ = ["rfut_rowwise", "supported"]

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


def _kernel(nb, n, x_ref, d_ref, h2_ref, o_ref):
    """D-multiply → zero-pad → (I⊗H_F2) MXU contraction → (H_f1⊗I)
    butterfly → 1/√NB, one (tm, nb) tile."""
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
    # caught by tests/_hw_guards.py; H is ±1 so only the input
    # mantissa matters).  bf16 inputs are exact already.
    y = jax.lax.dot_general(
        x3.astype(h.dtype), h,
        (((2,), (0,)), ((), ())),
        precision=None if xdtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).reshape(tm, nb)
    # (H_f1 ⊗ I_F2): contiguous-halves butterfly on the VPU, f32.
    z = _butterfly_kron_eye(y, f1)
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
