"""Counter-based, random-access random number generation.

This is the TPU-native re-design of the reference's Random123/Threefry stream
(``base/randgen.hpp:17-197``, ``base/context.hpp:19-183``): sample *i* of a
stream is a pure function of ``(seed, base + i)`` — no sequential state.  Any
window of any logical random array can therefore be generated locally on any
shard without communication, which is the load-bearing idea behind the whole
sketching layer (a sketch matrix is never communicated; each shard realizes
the window it needs — ``sketch/dense_transform_data.hpp:68-152``).

Implementation: JAX's Threefry-2x32 block cipher, driven explicitly.  We hand
``threefry_2x32`` a count array ``concat([ctr_hi, ctr_lo])`` so that output
element *i* is the PRF of the 64-bit counter ``(ctr_hi[i] << 32) | ctr_lo[i]``
under the key — verified window-invariant (element value depends only on its
counter, never on the window shape).  Each 64-bit counter yields 64 random
bits (the two output words).  Distributions that need more than 64 bits per
sample draw from independent *lanes* (the lane index is mixed into the key),
mirroring how the reference's MicroURNG advances ``counter[3]`` for multiple
draws per sample (``base/context.hpp:80-92``).

Everything here is jit-compatible, works under GSPMD (the counter math is
elementwise over an iota, so XLA shards it with the output), and is
deterministic across device counts, platforms, and shardings.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry_2x32

__all__ = [
    "raw_bits",
    "window_bits",
    "sample",
    "sample_at",
    "sample_window",
    "chi2_lanes",
    "DISTRIBUTIONS",
]

_GOLDEN = 0x9E3779B9  # 32-bit golden-ratio constant for lane mixing.
_MASK32 = 0xFFFFFFFF


def _key(seed: int, lane: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Threefry key from (seed, lane).  Lane picks an independent stream."""
    seed = int(seed) % (1 << 64)
    k0 = np.uint32(seed & _MASK32)
    k1 = np.uint32(((seed >> 32) ^ (lane * _GOLDEN)) & _MASK32)
    return (jnp.uint32(k0), jnp.uint32(k1))


def _split64(value: int) -> tuple[np.uint32, np.uint32]:
    value = int(value) % (1 << 64)
    return np.uint32(value >> 32), np.uint32(value & _MASK32)


def _add64(a_hi, a_lo, b_hi, b_lo):
    """64-bit add on (hi, lo) uint32 pairs (elementwise, wrap-around)."""
    lo = a_lo + b_lo
    carry = (lo < a_lo).astype(jnp.uint32)
    hi = a_hi + b_hi + carry
    return hi, lo


def _mul_u32(a_hi, a_lo, c: int):
    """(64-bit value) * (32-bit constant c), keeping low 64 bits."""
    c = int(c) & _MASK32
    c_lo = jnp.uint32(c & 0xFFFF)
    c_hi = jnp.uint32(c >> 16)
    # a_lo * c via 16-bit limbs to capture the 64-bit product in uint32 math.
    a0 = a_lo & jnp.uint32(0xFFFF)
    a1 = a_lo >> 16
    p00 = a0 * c_lo                      # up to 32 bits
    p01 = a0 * c_hi                      # shifted 16
    p10 = a1 * c_lo                      # shifted 16
    p11 = a1 * c_hi                      # shifted 32
    mid = (p00 >> 16) + (p01 & jnp.uint32(0xFFFF)) + (p10 & jnp.uint32(0xFFFF))
    lo = (p00 & jnp.uint32(0xFFFF)) | (mid << 16)
    hi = p11 + (p01 >> 16) + (p10 >> 16) + (mid >> 16)
    hi = hi + a_hi * jnp.uint32(c)
    return hi, lo


def raw_bits(seed: int, base: int, num: int, lane: int = 0, offset=0):
    """64 random bits for counters ``base+offset .. base+offset+num`` as two
    uint32 arrays.

    Pure function of (seed, lane, counter): random access, no state.
    ``offset`` may be a traced scalar (< 2^32; shard-dependent window
    starts under ``shard_map``); ``base``/``num`` must be static.  Counter
    math is uint32-pair with explicit carries, so windows crossing 2^32
    stay exact.
    """
    idx = jax.lax.iota(jnp.uint32, num)
    b_hi, b_lo = _split64(base)
    hi, lo = _add64(
        jnp.uint32(b_hi), jnp.uint32(b_lo),
        jnp.uint32(0), jnp.asarray(offset, jnp.uint32),
    )
    hi, lo = _add64(hi, lo, jnp.uint32(0), idx)
    out = threefry_2x32(_key(seed, lane), jnp.concatenate([hi, lo]))
    return out[:num], out[num:]


def window_bits(
    seed: int,
    base: int,
    full_cols: int,
    row0,
    col0,
    rows: int,
    cols: int,
    lane: int = 0,
):
    """Bits for a (rows, cols) window of a row-major logical array.

    Element (i, j) uses counter ``base + (row0+i)*full_cols + (col0+j)`` —
    the same contract as ``dense_transform_data_t::realize_matrix_view``
    (``sketch/dense_transform_data.hpp:79-152``), so a sharded realization is
    bit-identical to the single-host one.

    ``row0``/``col0`` may be traced scalars (shard-dependent offsets under
    ``shard_map``); ``rows``/``cols``/``base``/``full_cols`` must be
    static.  All counter math is uint32-pair with explicit carries, so
    windows crossing 2^32 counter boundaries stay exact.
    """
    i = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 0)
    j = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 1)
    if isinstance(row0, int):
        row0 = np.uint32(row0 % (1 << 32))
    if isinstance(col0, int):
        col0 = np.uint32(col0 % (1 << 32))
    # counter = base + (row0+i)*full_cols + (col0+j), uint32-pair math.
    r_hi, r_lo = _mul_u32(jnp.uint32(0), i + jnp.uint32(row0), full_cols)
    hi, lo = _add64(r_hi, r_lo, jnp.uint32(0), j + jnp.uint32(col0))
    b_hi, b_lo = _split64(base)
    hi, lo = _add64(hi, lo, jnp.uint32(b_hi), jnp.uint32(b_lo))
    out = threefry_2x32(
        _key(seed, lane), jnp.concatenate([hi.ravel(), lo.ravel()])
    )
    n = rows * cols
    return out[:n].reshape(rows, cols), out[n:].reshape(rows, cols)


# ---------------------------------------------------------------------------
# bits -> distribution values
# ---------------------------------------------------------------------------


def _uniform01(hi, lo, dtype):
    """Uniform in (0, 1) — open at both ends so logs/inverse-CDFs are safe.

    ``(k + 0.5) * 2^-bits`` with k an integer below the mantissa width is
    exact in floating point (no rounding), so the result lies in
    ``[2^-(bits+1), 1 - 2^-(bits+1)]`` and can never round to 0.0 or 1.0.
    """
    dtype = jnp.dtype(dtype)
    if dtype == jnp.float64:
        if not jax.config.jax_enable_x64:
            raise ValueError(
                "float64 sampling requires jax_enable_x64; enable it or "
                "request float32"
            )
        # 52 mantissa bits from the two words: exact (k + 0.5) * 2^-52.
        top = hi.astype(jnp.uint64) >> 7       # 25 bits
        bot = lo.astype(jnp.uint64) >> 5       # 27 bits
        k = (top << 27 | bot).astype(jnp.float64)
        return (k + 0.5) * (2.0 ** -52)
    # f32 leads with HI's top bits — the SAME leading bits as the f64
    # value, so the two dtype streams agree to ~2^-24 (cross-precision
    # determinism: an f32 TPU run and an f64/native-C run see the same
    # uniforms).  A lo-based f32 stream would be statistically independent
    # of the f64 one and silently break cross-language parity.
    k = (hi >> 8).astype(jnp.float32)          # 24 bits, exact in f32
    return ((k + np.float32(0.5)) * np.float32(2.0 ** -24)).astype(dtype)


def _uniform(hi, lo, dtype, low=0.0, high=1.0):
    return _uniform01(hi, lo, dtype) * (high - low) + low


def _normal(hi, lo, dtype):
    """Box-Muller from the counter's two 32-bit words — exact N(0, 1), one
    counter per sample.

    ~5x cheaper on the TPU VPU than the inverse-CDF (ndtri) route, which
    matters because sketch-operand generation rides the matmul's critical
    path.  u1/u2 use the (k + 0.5)·2^-b construction (exact, never 0/1):
    24 bits each in f32, 32 bits each in f64 (tail reach ~6.6 sigma).
    """
    dtype = jnp.dtype(dtype)
    if dtype == jnp.float64:
        if not jax.config.jax_enable_x64:
            raise ValueError(
                "float64 sampling requires jax_enable_x64; enable it or "
                "request float32"
            )
        u1 = (hi.astype(jnp.float64) + 0.5) * (2.0**-32)
        u2 = (lo.astype(jnp.float64) + 0.5) * (2.0**-32)
    else:
        u1 = ((hi >> 8).astype(jnp.float32) + np.float32(0.5)) * np.float32(2.0**-24)
        u2 = ((lo >> 8).astype(jnp.float32) + np.float32(0.5)) * np.float32(2.0**-24)
    z = jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(2.0 * np.pi * u2)
    return z.astype(dtype)


def _cauchy(hi, lo, dtype):
    u = _uniform01(hi, lo, dtype)
    return jnp.tan(jnp.pi * (u - 0.5)).astype(dtype)


def _rademacher(hi, lo, dtype):
    return jnp.where(lo & 1, 1.0, -1.0).astype(dtype)


def _exponential(hi, lo, dtype):
    u = _uniform01(hi, lo, dtype)
    return -jnp.log(u).astype(dtype)


def _levy(hi, lo, dtype):
    # Standard Lévy: 1 / chi2(1) = 1 / Z^2   (utility/distributions.hpp:17-35).
    z = _normal(hi, lo, dtype)
    return (1.0 / (z * z)).astype(dtype)


def _uniform_int(hi, lo, dtype, low=0, high=None):
    """Uniform integer in [low, high] inclusive (matching boost's
    uniform_int_distribution used at hash_transform_data.hpp:66-73).

    Uses a 64-bit multiply-shift (floor(x * span / 2^64) with x the full
    64-bit counter hash), so the residual bias is O(span * 2^-64) — far
    below statistical visibility — and no uint64 dtype is needed.
    """
    if high is None:
        raise ValueError("uniform_int requires an explicit 'high' bound")
    low, high = int(low), int(high)
    if high < low:
        raise ValueError(f"uniform_int needs low <= high, got [{low}, {high}]")
    span = high - low + 1
    if span > (1 << 32):
        raise ValueError(f"uniform_int span {span} exceeds 2^32")
    # x*span >> 64 via two 32x32->64 partial products in uint32-pair math.
    p1_hi, p1_lo = _mul_u32(jnp.uint32(0), hi, span)
    p2_hi, _p2_lo = _mul_u32(jnp.uint32(0), lo, span)
    s_hi, _s_lo = _add64(p1_hi, p1_lo, jnp.uint32(0), p2_hi)
    return (jnp.int64(low) + s_hi if jax.config.jax_enable_x64
            else low + s_hi.astype(jnp.int32) if high < (1 << 31)
            else low + s_hi).astype(dtype)


def chi2_lanes(seed: int, base: int, size: int, dof: int, dtype=jnp.float32):
    """χ²(dof) samples as a sum of ``dof`` squared-normal lanes over one
    reserved counter block (lanes 1..dof; lane 0 left for the caller).

    Used by the Matérn feature maps' multivariate-t row correction
    (``sqrt(2ν/χ²_{2ν})``, ≙ ``sketch/RFT_data.hpp:336-345``).
    """
    if dof < 1 or int(dof) != dof:
        raise ValueError(f"chi2_lanes needs a positive integer dof, got {dof}")
    acc = jnp.zeros((size,), dtype)
    for lane in range(int(dof)):
        z = sample("normal", seed, base, size, dtype=dtype, lane=lane + 1)
        acc = acc + z * z
    return acc


DISTRIBUTIONS = {
    "uniform": _uniform,
    "normal": _normal,
    "cauchy": _cauchy,
    "rademacher": _rademacher,
    "exponential": _exponential,
    "levy": _levy,
    "uniform_int": _uniform_int,
}


def sample(
    dist: str,
    seed: int,
    base: int,
    num: int,
    dtype=jnp.float32,
    lane: int = 0,
    offset=0,
    **params: Any,
):
    """1-D stream sample: values for counters ``base+offset ..
    base+offset+num`` (``offset`` may be traced — see :func:`raw_bits`)."""
    hi, lo = raw_bits(seed, base, num, lane, offset=offset)
    return DISTRIBUTIONS[dist](hi, lo, dtype, **params)


def sample_at(
    dist: str,
    seed: int,
    base: int,
    idx,
    dtype=jnp.float32,
    lane: int = 0,
    **params: Any,
):
    """Values of a 1-D stream at the counters ``base + idx`` (``idx`` an
    integer array of any shape, every entry below 2^32): element-wise the
    same bits as :func:`sample`'s window at those counters, so a sketch
    reads its draws at scattered coordinates (a sparse operand's column
    ids) without realizing the whole stream or gathering from it."""
    idx = jnp.asarray(idx)
    b_hi, b_lo = _split64(base)
    hi, lo = _add64(jnp.uint32(b_hi), jnp.uint32(b_lo),
                    jnp.uint32(0), idx.astype(jnp.uint32).ravel())
    out = threefry_2x32(_key(seed, lane), jnp.concatenate([hi, lo]))
    n = idx.size
    return DISTRIBUTIONS[dist](out[:n], out[n:], dtype, **params).reshape(idx.shape)


def sample_window(
    dist: str,
    seed: int,
    base: int,
    full_shape: tuple[int, int],
    dtype=jnp.float32,
    offset: tuple[int, int] = (0, 0),
    shape: tuple[int, int] | None = None,
    lane: int = 0,
    **params: Any,
):
    """Window of a logical row-major 2-D random array.

    ``sample_window(d, s, b, (R, C))`` == full matrix; any sub-window of it is
    bit-identical to the corresponding slice, enabling shard-local sketch
    realization (reference invariant: ``base/random_matrices.hpp:22-177``).
    """
    rows_full, cols_full = full_shape
    if shape is None:
        shape = (rows_full - offset[0], cols_full - offset[1])
    hi, lo = window_bits(
        seed, base, cols_full, offset[0], offset[1], shape[0], shape[1], lane
    )
    return DISTRIBUTIONS[dist](hi, lo, dtype, **params)
