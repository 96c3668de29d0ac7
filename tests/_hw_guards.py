"""Hardware numerics guards: compiled kernels and TPU-precision paths
against references, each a plain function that raises on failure.

``chip_smoke.py`` calls every guard of :data:`GUARDS` in-process during
its sketch phase (one process holds the chip).  They need a TPU: the
compiled Pallas kernels and the MXU's default precision are what they
guard, and neither exists on the CPU.
"""

from __future__ import annotations

import os
from contextlib import contextmanager


@contextmanager
def _env_restored():
    """Guards toggle SKYLARK_* gates; running in one process means those
    mutations would leak into later guards — snapshot and restore."""
    saved = {
        k: os.environ.get(k)
        for k in (
            "SKYLARK_NO_FRFT_GEMM",
            "SKYLARK_NO_PALLAS",
            "SKYLARK_NO_SRHT_GEMM",
            "SKYLARK_NO_PPT_DFT",
        )
    }
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def guard_rfut_rowwise_compiled():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from libskylark_tpu.sketch import pallas_fut
    from libskylark_tpu.sketch.fut import wht

    rng = np.random.default_rng(0)
    m, n, nb = 256, 512, 512
    x = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    d = jnp.asarray(np.sign(rng.standard_normal(n)), jnp.float32)
    out = pallas_fut.rfut_rowwise(x, d, nb, interpret=False)  # compiled
    ref = wht(x * d[None, :], axis=1)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
    )


def guard_bf16_split_accuracy():
    """An astype-based split (``x - bf16(x)``) collapses to single-bf16
    accuracy on TPU (XLA elides the f32→bf16→f32 convert pair); the
    bit-mask split must hold ~f32 accuracy on hardware."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from libskylark_tpu.core.context import SketchContext
    from libskylark_tpu.sketch.fjlt import FJLT
    from libskylark_tpu.sketch.hash import CWT

    rng = np.random.default_rng(0)
    n, s, m = 1024, 256, 512
    A = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    S = FJLT(n, s, SketchContext(seed=3))
    assert S._gemm_wins(jnp.float32)
    out = np.asarray(
        jax.jit(lambda A: S._apply_srht_gemm(A, rowwise=True))(A), np.float64
    )
    G = np.asarray(S._srht_matrix(jnp.float32), np.float64)
    ref = (np.asarray(A, np.float64) @ G) / np.sqrt(s)
    rel = np.abs(out - ref).max() / np.abs(ref).max()
    assert rel < 2e-5, f"FJLT split degraded on hardware: {rel}"
    Sc = CWT(m, 64, SketchContext(seed=5))
    outc = np.asarray(
        jax.jit(lambda A: Sc.apply(A, "columnwise"))(A), np.float64
    )
    M = np.asarray(Sc._hash_matrix(jnp.float32), np.float64)
    refc = M.T @ np.asarray(A, np.float64)
    relc = np.abs(outc - refc).max() / np.abs(refc).max()
    assert relc < 2e-5, f"CWT split degraded on hardware: {relc}"


def guard_wht_f32_accuracy():
    """Guards the MXU default-precision hazard in the WHT chain."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from libskylark_tpu.sketch.fut import _hadamard, wht

    rng = np.random.default_rng(2)
    m, n = 256, 4096
    x = rng.standard_normal((m, n)).astype(np.float32)
    got = np.asarray(
        jax.jit(lambda x: wht(x, axis=1))(jnp.asarray(x)), np.float64
    )
    H = np.asarray(_hadamard(12), np.float64)
    ref = (x.astype(np.float64) @ H.T) / np.sqrt(n)
    rel = np.abs(got - ref).max() / np.abs(ref).max()
    assert rel < 2e-5, f"wht f32 degraded on hardware: {rel}"


def guard_psd_gram_precision():
    """`ml/krr.py::_psd_gram` must keep its precision='highest' pin."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from libskylark_tpu.ml.krr import _psd_gram

    rng = np.random.default_rng(3)
    m, s = 4096, 256
    Z = jnp.asarray(rng.standard_normal((m, s)), jnp.float32)
    lam = jnp.float32(1e-4)
    G = np.asarray(
        jax.jit(lambda Z: _psd_gram(Z.T, Z) + lam * jnp.eye(s))(Z), np.float64
    )
    ref = (
        np.asarray(Z, np.float64).T @ np.asarray(Z, np.float64)
        + 1e-4 * np.eye(s)
    )
    rel = np.abs(G - ref).max() / np.abs(ref).max()
    assert rel < 2e-5, f"_psd_gram degraded on hardware: {rel}"
    L = np.linalg.cholesky(G)  # PSD property survives
    assert np.isfinite(L).all()


def guard_streaming_svd_orthogonality():
    """U orthonormal to ~1e-3 in f32; an un-pinned Gram sends it ~1e-2."""
    import jax.numpy as jnp
    import numpy as np

    from libskylark_tpu import SketchContext
    from libskylark_tpu.linalg import (
        SVDParams,
        streaming_approximate_svd,
        synthetic_lowrank_blocks,
    )

    m, n, k, br = 100_000, 256, 20, 25_000
    blocks = synthetic_lowrank_blocks(
        SketchContext(seed=5), m, n, k, noise=0.01, dtype=jnp.float32
    )
    U, s, V = streaming_approximate_svd(
        blocks, (m, n), k, SketchContext(seed=6),
        SVDParams(num_iterations=1), block_rows=br, materialize_u=True,
    )
    G = np.asarray(jnp.dot(U.T, U, precision="highest"), np.float64)
    err = np.abs(G - np.eye(k)).max()
    assert err < 1.5e-3, f"streaming-SVD U lost orthogonality: {err}"


def guard_frft_realized_split():
    """Fastfood realized-W f32 4-pass split vs the streaming form."""
    import jax.numpy as jnp
    import numpy as np

    from libskylark_tpu import SketchContext
    from libskylark_tpu.sketch import FastGaussianRFT

    rng = np.random.default_rng(4)
    n, s, m = 512, 1024, 4096
    A = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    S = FastGaussianRFT(n, s, SketchContext(seed=7), sigma=2.0)
    assert S._realize_wins(jnp.float32, m)
    with _env_restored():
        fast = np.asarray(S.apply(A, "rowwise"))
        os.environ["SKYLARK_NO_FRFT_GEMM"] = "1"
        ref = np.asarray(S.apply(A, "rowwise"))
    err = np.abs(fast - ref).max()
    assert err < 5e-4, f"FRFT realized split degraded on hardware: {err}"


def guard_mmt_scaled_onehot_split():
    """MMT scaled-one-hot f32 path vs the f64 host oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from libskylark_tpu import SketchContext
    from libskylark_tpu.sketch import MMT

    rng = np.random.default_rng(5)
    n, s, m = 1024, 128, 512
    A = jnp.asarray(rng.standard_normal((n, m)), jnp.float32)
    S = MMT(n, s, SketchContext(seed=9))
    out_d = np.asarray(
        jax.jit(lambda A: S.apply(A, "columnwise"))(A), np.float64
    )
    M = np.asarray(S._hash_matrix(jnp.float32), np.float64)
    ref = M.T @ np.asarray(A, np.float64)
    rel = np.abs(out_d - ref).max() / np.abs(ref).max()
    assert rel < 5e-5, f"MMT scaled split degraded on hardware: {rel}"


def guard_fjlt_pallas_branch_compiled():
    import jax.numpy as jnp
    import numpy as np

    from libskylark_tpu import SketchContext
    from libskylark_tpu.sketch import FJLT

    rng = np.random.default_rng(1)
    n, s, m = 512, 64, 256
    A = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    S1 = FJLT(n, s, SketchContext(seed=3))
    with _env_restored():
        out = S1.apply(A, "rowwise")  # gate picks a TPU path
        os.environ["SKYLARK_NO_PALLAS"] = "1"
        os.environ["SKYLARK_NO_SRHT_GEMM"] = "1"
        ref = S1.apply(A, "rowwise")  # forced XLA path, same transform
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
    )


def guard_sparse_dense_out_scatter():
    """The flat scatter of the sparse CWT ``dense_output`` apply
    (``jax.ops.segment_sum``) against the dense apply of the same
    matrix, at 40,000 entries into 2048 x 64."""
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import sparse as jsparse

    from libskylark_tpu import SketchContext
    from libskylark_tpu.sketch import CWT

    rng = np.random.default_rng(5)
    n, m, s = 1 << 17, 64, 2048
    nnz = 40_000
    flat = rng.choice(n * m, nnz, replace=False)  # distinct (row, col)
    rows, cols = flat // m, flat % m
    vals = rng.standard_normal(nnz).astype(np.float32)
    A = jsparse.BCOO(
        (jnp.asarray(vals), jnp.asarray(np.stack([rows, cols], 1))),
        shape=(n, m),
    )
    S = CWT(n, s, SketchContext(seed=9))
    out = np.asarray(S.apply(A, "columnwise", dense_output=True))
    dense = np.zeros((n, m), np.float32)
    np.add.at(dense, (rows, cols), vals)
    M = np.asarray(S._hash_matrix(jnp.float32), np.float64)
    ref = M.T @ dense.astype(np.float64)
    rel = np.abs(out - ref).max() / np.abs(ref).max()
    assert rel < 1e-5, f"sparse dense-out scatter diverged on hardware: {rel}"


def guard_pallas_window_compiled():
    """The windowed row scatter-add kernel must compile (Mosaic) and
    match segment_sum on hardware — its scalar-indexed VECTOR
    read-modify-write on the VMEM scratch is exactly the construct
    Mosaic may refuse on some TPU generations, and interpret-mode CPU
    parity cannot see that.  Also pins the fused-chunk contract on
    hardware: the acc-folded emit must be BITWISE equal to kernel +
    separate add (one IEEE add either way)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from libskylark_tpu.sketch.pallas_window import (
        scatter_rows,
        self_check,
        supported,
    )

    k, s, m = 65_536, 1024, 256
    assert supported(k, s, m)
    err = self_check(k, s, m)
    assert err < 1e-5, f"pallas window kernel diverged on hardware: {err}"
    kb, kv, ka, kacc = jax.random.split(jax.random.PRNGKey(17), 4)
    b = jax.random.randint(kb, (k,), 0, s, jnp.int32)
    v = jax.random.normal(kv, (k,), jnp.float32)
    A = jax.random.normal(ka, (k, m), jnp.float32)
    acc = jax.random.normal(kacc, (s, m), jnp.float32)
    fused = scatter_rows(A, b, v, s, acc=acc)
    unfused = acc + scatter_rows(A, b, v, s)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(unfused))


def guard_fjlt_two_step_kernel():
    """``FJLT._apply_pallas`` on the default route — the fused
    D·x → WHT kernel, then the XLA sampled gather (the in-kernel sampled
    epilogue is not a default route: Mosaic has no lane gather across
    vregs) — against the subsampled-Hadamard matmul route of the same
    transform, at NB=4096, S=1024."""
    import jax.numpy as jnp
    import numpy as np

    from libskylark_tpu import SketchContext
    from libskylark_tpu.sketch import FJLT

    m, n, s = 256, 4096, 1024
    rng = np.random.default_rng(1)
    A = jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))
    S1 = FJLT(n, s, SketchContext(seed=4))
    out = np.asarray(S1._apply_pallas(A))
    ref = np.asarray(S1._apply_srht_gemm(A, True))
    err = np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-30)
    assert err < 1e-5, f"two-step FJLT kernel diverged on hardware: {err}"


GUARDS = [
    ("rfut_rowwise_compiled", guard_rfut_rowwise_compiled),
    ("sparse_dense_out_scatter", guard_sparse_dense_out_scatter),
    ("pallas_window_compiled", guard_pallas_window_compiled),
    ("fjlt_two_step_kernel", guard_fjlt_two_step_kernel),
    ("bf16_split_accuracy", guard_bf16_split_accuracy),
    ("wht_f32_accuracy", guard_wht_f32_accuracy),
    ("psd_gram_precision", guard_psd_gram_precision),
    ("streaming_svd_orthogonality", guard_streaming_svd_orthogonality),
    ("frft_realized_split", guard_frft_realized_split),
    ("mmt_scaled_onehot_split", guard_mmt_scaled_onehot_split),
    ("fjlt_pallas_branch_compiled", guard_fjlt_pallas_branch_compiled),
]
