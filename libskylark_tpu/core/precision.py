"""Precision utilities for riding the bf16 MXU with f32 data.

An f32 value splits exactly into three bf16-representable parts by
masking mantissa bits: ``x = hi + lo + lo2`` with each part carrying ≤8
leading mantissa bits.  Contracting each part against a bf16-exact
operand (±1 / small-integer sketch matrices) with f32 accumulation and
summing reproduces full f32 precision at ~3× the f32 matmul rate.

The split is built from integer bit-masking, NOT ``astype`` round-trips:
XLA's excess-precision rules elide ``f32→bf16→f32`` convert pairs (the
upcast-after-downcast is "at least as precise", so the compiler drops
it), which silently turns ``x - bf16(x)`` into zero on TPU and collapses
an astype-based split to single-bf16 accuracy — measured 1.6e-3 max-rel
on hardware vs 8e-8 for this formulation (tests/_hw_guards.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["bf16_split3", "f32_accumulable", "long_dot"]


# Terms one MXU product sums at a time in :func:`long_dot`.
LONG_DOT_BLOCK = 4096


@jax.jit
def long_dot(A, B):
    """A·B for A (p, n), B (n, q) with a long n: ``LONG_DOT_BLOCK`` terms
    at a time at ``precision="highest"`` with a ≥f32 accumulator, the
    blocks' products added in that dtype.

    One f32 product at ``highest`` over 49,152 terms on a v5e's MXU was
    off by 7.2e-6 of the product's norm; 4,096 terms at a time by 6.0e-8,
    what float64 says of f32 (PERF.md section 6, PR 31).  The first is
    enough for a least-squares residual; it is not for a difference of
    two such products that has to be right to 5e-7 of its terms (the
    Woodbury preconditioner of ``ml/krr.py``: CG stalled on an
    indefinite M).  Up to one block this is the plain product."""
    n = A.shape[1]
    block = min(LONG_DOT_BLOCK, n)
    acc_dtype = jnp.promote_types(jnp.promote_types(A.dtype, B.dtype), jnp.float32)

    def part(start, size):
        return jnp.dot(
            jax.lax.dynamic_slice_in_dim(A, start, size, 1),
            jax.lax.dynamic_slice_in_dim(B, start, size, 0),
            precision="highest", preferred_element_type=acc_dtype,
        )

    if block == n:
        return part(0, n)
    acc = jax.lax.fori_loop(
        1, n // block, lambda i, acc: acc + part(i * block, block), part(0, block)
    )
    return acc + part(n - n % block, n % block) if n % block else acc


def f32_accumulable(dtype, *, demote_f64: bool = False) -> bool:
    """True when ``dtype`` may ride an f32-accumulating kernel with
    casts at the boundary.  bf16/f16 qualify unconditionally — f32 is a
    strict superset of both, so the cast in is exact and only the final
    cast out rounds (no worse than accumulating natively in the narrow
    type, and usually much better).  f64 qualifies only when the caller
    explicitly accepts the demotion (``demote_f64=True``, i.e. a
    force-enabled kernel): x64 parity runs must keep the XLA
    full-precision lowering by default.  This is the dtype gate of the
    Pallas window scatter (``sketch/pallas_window.py``) — the precision
    ladder hands out bf16 operands, which would otherwise force every
    hash scatter back to XLA."""
    dt = jnp.dtype(dtype)
    if dt in (
        jnp.dtype(jnp.float32),
        jnp.dtype(jnp.bfloat16),
        jnp.dtype(jnp.float16),
    ):
        return True
    if dt == jnp.dtype(jnp.float64):
        return bool(demote_f64)
    return False


def _mask_top(x):
    """The top-16-bit (sign+exponent+7 mantissa) part of f32 x — exactly
    representable in bf16; computed by integer masking so no convert pair
    exists for XLA to elide."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(
        bits & jnp.uint32(0xFFFF0000), jnp.float32
    )


def bf16_split3(x):
    """``(hi, lo, lo2)`` bf16 arrays with ``hi + lo + lo2 ≈ x`` to ~2^-24
    relative.  ``x`` must be f32 — the split bitcasts, so value-convert
    other dtypes first (an int bit pattern would masquerade as floats).

    Magnitude contract: the ~2^-24-relative bound holds for
    ``|x| ≳ 2^-110``.  Below that, ``lo``/``lo2`` (whose exponents sit
    ~8/16 binades under ``x``'s) fall beneath bf16's subnormal floor
    (2^-133; f32 reaches 2^-149) and round to zero, so the split
    gracefully degrades toward single-bf16 relative accuracy as ``|x|``
    approaches f32's own subnormal range.  Harmless for sketching
    workloads — inputs that tiny are already below any sketch tolerance —
    but callers needing the full contract at extreme denormal scales
    should pre-scale (round-2 advisor finding)."""
    if x.dtype != jnp.float32:
        raise TypeError(
            f"bf16_split3 needs float32 input, got {x.dtype}; astype first"
        )
    hi = _mask_top(x)
    r1 = x - hi
    lo = _mask_top(r1)
    lo2 = r1 - lo
    return (
        hi.astype(jnp.bfloat16),
        lo.astype(jnp.bfloat16),
        lo2.astype(jnp.bfloat16),
    )
