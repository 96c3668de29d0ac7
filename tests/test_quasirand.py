"""Leaped-Halton quasirandom core (ISSUE PR 14 satellite): window
determinism across execution modes, serialization round-trip, and
parameter validation.

The determinism contract is deliberately two-tier: WITHIN a mode
(eager-vs-eager, jit-vs-jit) windows are bitwise reproducible — that is
what the plan cache and the QJLT interchange lean on — while ACROSS
modes XLA may fuse the digit recurrence differently, so jit-vs-eager is
pinned to allclose at a few ulp, not bit equality.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import enable_x64

from libskylark_tpu.core.quasirand import LeapedHaltonSequence, primes
from libskylark_tpu.utils.exceptions import InvalidParameters


def test_window_deterministic_within_each_mode():
    seq = LeapedHaltonSequence(40)

    def eager():
        return np.asarray(seq.window(100, 32))

    jitted = jax.jit(
        lambda: seq.window(100, 32), static_argnums=()
    )

    e1, e2 = eager(), eager()
    np.testing.assert_array_equal(e1, e2)
    j1 = np.asarray(jitted())
    j2 = np.asarray(jax.jit(lambda: seq.window(100, 32))())
    np.testing.assert_array_equal(j1, j2)
    # cross-mode: same values up to a few ulp, NOT pinned bitwise
    np.testing.assert_allclose(e1, j1, rtol=0, atol=4 * np.finfo(np.float32).eps)


def test_window_values_are_halton():
    """Spot-check against the textbook definition: base-2 and base-3
    radical inverses of ``idx*leap + 1`` (the sequence skips the all-zero
    index-0 point, as the reference does)."""
    seq = LeapedHaltonSequence(2, leap=1)
    w = np.asarray(seq.window(1, 4, dtype=jnp.float32))

    def rad(p, i):
        f, r = 1.0, 0.0
        while i:
            f /= p
            r += f * (i % p)
            i //= p
        return r

    expect = np.array(
        [[rad(2, i), rad(3, i)] for i in range(2, 6)], np.float32
    )
    np.testing.assert_allclose(w, expect, atol=1e-6)


def test_json_round_trip_preserves_windows_incl_dtype():
    seq = LeapedHaltonSequence(24, leap=101)
    back = LeapedHaltonSequence.from_json(seq.to_json())
    assert back == seq  # frozen dataclass: d and leap both survive
    np.testing.assert_array_equal(
        np.asarray(seq.window(7, 16, dtype=jnp.float32)),
        np.asarray(back.window(7, 16, dtype=jnp.float32)),
    )
    with enable_x64():
        a = seq.window(7, 16, dtype=jnp.float64)
        b = back.window(7, 16, dtype=jnp.float64)
        assert a.dtype == jnp.float64 and b.dtype == jnp.float64
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dict_round_trip_fields():
    d = LeapedHaltonSequence(5, leap=13).to_dict()
    assert d["sequence_type"] == "leaped halton"
    assert d["d"] == 5 and d["leap"] == 13
    assert LeapedHaltonSequence.from_dict(d) == LeapedHaltonSequence(5, 13)


def test_default_leap_is_next_prime_and_coprime():
    for dim in (1, 4, 10, 40):
        seq = LeapedHaltonSequence(dim)
        assert seq.leap == int(primes(dim + 1)[-1])
        assert all(seq.leap % int(p) for p in primes(dim))


def test_negative_dimension_rejected():
    with pytest.raises(InvalidParameters, match="dimension"):
        LeapedHaltonSequence(-1)


def test_nonpositive_leap_rejected():
    for leap in (0, -2):
        with pytest.raises(InvalidParameters, match="positive"):
            LeapedHaltonSequence(4, leap=leap)


def test_leap_sharing_base_factor_rejected():
    # d=3 → bases (2, 3, 5); leap 6 shares factors with 2 AND 3
    with pytest.raises(InvalidParameters, match=r"coprime.*\[2, 3\]"):
        LeapedHaltonSequence(3, leap=6)
    # 7 is coprime with all of (2, 3, 5): accepted
    assert LeapedHaltonSequence(3, leap=7).leap == 7
