"""Pallas fused RFUT kernel vs XLA path (interpret mode on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from libskylark_tpu import SketchContext
from libskylark_tpu.sketch import FJLT, wht
from libskylark_tpu.sketch import pallas_fut


class TestPallasRFUT:
    @pytest.mark.slow
    @pytest.mark.parametrize("n,nb", [(4096, 4096), (200, 256), (2048, 2048)])
    def test_matches_xla_wht(self, rng, n, nb):
        m = 16
        x = jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))
        d = jnp.asarray(np.sign(rng.standard_normal(n)).astype(np.float32))
        out = pallas_fut.rfut_rowwise(x, d, nb, interpret=True)
        xp = jnp.pad(x * d[None, :], ((0, 0), (0, nb - n)))
        ref = wht(xp, axis=1)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
        )

    def test_supported_predicate(self):
        assert pallas_fut.supported(1024, 4096, 4096)
        assert not pallas_fut.supported(7, 4096, 4096)  # rows not tileable
        assert not pallas_fut.supported(64, 100, 100)  # not pow2
        assert not pallas_fut.supported(64, 128, 128)  # below 2*F2
        assert not pallas_fut.supported(64, 1 << 18, 1 << 18)  # too large

    def test_fjlt_pallas_path_matches_xla(self, rng):
        n, s, m = 512, 64, 32
        A = jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))
        S1 = FJLT(n, s, SketchContext(seed=3))
        ref = S1.apply(A, "rowwise")  # XLA path (CPU backend)
        out = S1._apply_pallas(A, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
        )

    @pytest.mark.parametrize("dim,shape", [
        ("rowwise", (32, 512)), ("columnwise", (512, 32)),
    ])
    def test_fjlt_real_dispatch_via_interpret(self, rng, monkeypatch, dim, shape):
        # Exercise apply()'s ACTUAL Pallas branch conditions (not a
        # hand-copied dispatch): force the gate open and run the kernel in
        # interpret mode on CPU.
        import libskylark_tpu.sketch.fjlt as fjlt_mod

        n, s = 512, 64
        A = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
        S1 = FJLT(n, s, SketchContext(seed=4))
        ref = S1.apply(A, dim)  # XLA path (gate closed on CPU)
        monkeypatch.setattr(fjlt_mod, "_use_pallas", lambda: True)
        orig = S1._apply_pallas
        monkeypatch.setattr(
            FJLT, "_apply_pallas",
            lambda self, B, interpret=False: orig(B, interpret=True),
        )
        out = S1.apply(A, dim)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
        )

    def test_unsupported_shape_raises_value_error(self, rng):
        """A shape the gate rejects must fail with a pointer to the
        predicate, not an opaque TypeError from `m // None`."""
        m, nb = 7, 512  # no tile divides m=7
        assert pallas_fut._tile_rows(m, nb) is None
        x = jnp.asarray(rng.standard_normal((m, nb)).astype(np.float32))
        d = jnp.asarray(np.sign(rng.standard_normal(nb)).astype(np.float32))
        with pytest.raises(ValueError, match="check supported"):
            pallas_fut.rfut_rowwise(x, d, nb, interpret=True)
