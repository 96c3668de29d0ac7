"""Streaming kernel-ridge demo (rows AND features streamed).

The single-chip machinery behind the 10M×4096 north-star
(``BASELINE.json``): ``streaming_kernel_ridge`` never holds X or a feature
chunk — ``block_fn`` yields row panels (here sliced from a small
in-memory X; at scale, counter-generated or IO-backed), features are
regenerated per panel, and only one panel plus the (n, t) residual is
resident.  Checks predictions against ``large_scale_kernel_ridge`` on
the same data (identical BCD updates from the same context).

Run: python examples/streaming_krr_demo.py [n] [d] [features]
"""

import os
import sys

# runnable from anywhere: repo root is one level up
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from libskylark_tpu import SketchContext
from libskylark_tpu.ml import (
    GaussianKernel,
    KrrParams,
    large_scale_kernel_ridge,
    streaming_kernel_ridge,
)


def block_fn(start, rows, X):
    """A row panel of X.  Module-level, X through ``block_args``: the
    trainer's programs are then built once a process, not once a call."""
    return jax.lax.dynamic_slice_in_dim(X, start, rows, axis=0)


def main():
    n, d, s = (
        int(x) for x in (sys.argv[1:4] + [4096, 32, 256][len(sys.argv) - 1 :])
    )
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    y = jnp.asarray(np.tanh(np.asarray(X) @ rng.standard_normal(d)), jnp.float32)
    kernel = GaussianKernel(d, sigma=float(np.sqrt(d)))
    params = KrrParams(max_split=s // 2, iter_lim=15, tolerance=1e-7)

    model = streaming_kernel_ridge(
        kernel, block_fn, (n, d), y, 0.1, s, SketchContext(seed=7),
        params, block_rows=max(256, n // 16), feature_dtype=jnp.float32,
        block_args=(X,),
    )
    pred = np.asarray(model.predict(X))[:, 0]
    print(f"streaming KRR: n={n} d={d} s={s}, "
          f"corr(pred, y) = {np.corrcoef(pred, np.asarray(y))[0, 1]:.4f}")

    ref = large_scale_kernel_ridge(
        kernel, X, y, 0.1, s, SketchContext(seed=7), params
    )
    rel = np.abs(pred - np.asarray(ref.predict(X))[:, 0]).max() / (
        np.abs(pred).max() + 1e-30
    )
    print(f"vs large_scale_kernel_ridge (same context): max rel {rel:.2e}")


if __name__ == "__main__":
    main()
