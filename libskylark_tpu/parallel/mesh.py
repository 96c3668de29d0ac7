"""Device mesh and sharding helpers.

The reference's matrix distributions (Elemental's ``[MC,MR]``, ``[VC,*]``,
``[*,VC]``, ``[*,*]``, ``[CIRC,CIRC]`` — see SURVEY §2.7) map onto named
meshes + `PartitionSpec`s:

=================  ==========================================
Elemental          TPU equivalent
=================  ==========================================
``[MC,MR]``        2-D mesh, ``P(ROWS, COLS)``
``[VC,*]/[VR,*]``  1-D (or flattened 2-D) mesh, ``P(ROWS, None)``
``[*,VC]/[*,VR]``  ``P(None, COLS)``
``[*,*]``          fully replicated, ``P()``
``[CIRC,CIRC]``    host-gathered (only at API boundaries)
=================  ==========================================

Multi-host: callers run ``jax.distributed.initialize()`` before building a
mesh; everything below is host-count agnostic (``jax.devices()`` is global).
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "ROWS",
    "COLS",
    "make_mesh",
    "default_mesh",
    "sharding",
    "shard",
    "shard_rows",
    "shard_rows_padded",
    "shard_cols",
    "replicate",
    "fully_replicated",
]

# Canonical axis names: ROWS shards the long/sample dimension (≙ [VC,*]
# row distribution / MC grid rows), COLS the feature dimension (≙ MR).
ROWS = "rows"
COLS = "cols"


def make_mesh(
    shape: Sequence[int],
    axis_names: Sequence[str] = (ROWS, COLS),
    explicit: bool = False,
) -> Mesh:
    """Build a mesh of the given shape over all visible devices.

    Axes default to ``AxisType.Auto``: shardings placed on inputs propagate
    through jitted code with GSPMD choosing the communication schedule —
    the design stance of SURVEY §2.7 P4 (the reference hand-picks
    matrix-panel/panel-matrix/inner/outer GEMM schedules; XLA does this
    automatically).  Pass ``explicit=True`` for JAX's typed-sharding mode
    where every contraction must name its output sharding.
    """
    kind = (
        jax.sharding.AxisType.Explicit
        if explicit
        else jax.sharding.AxisType.Auto
    )
    return jax.make_mesh(
        tuple(shape), tuple(axis_names), axis_types=(kind,) * len(shape)
    )


def default_mesh(n_devices: int | None = None) -> Mesh:
    """Near-square 2-D (ROWS, COLS) mesh over the visible devices.

    ≙ Elemental's default approximately-square process grid
    (``El::Grid(comm)``).  A single device yields a 1x1 mesh, so all code
    paths are mesh-agnostic.
    """
    n = len(jax.devices()) if n_devices is None else int(n_devices)
    r = int(math.isqrt(n))
    while n % r:
        r -= 1
    return make_mesh((r, n // r), (ROWS, COLS))


def sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def shard(x, mesh: Mesh, *spec):
    """Place ``x`` with the given PartitionSpec entries."""
    return jax.device_put(x, sharding(mesh, *spec))


def row_sharding(mesh: Mesh, ndim: int = 2) -> NamedSharding:
    """The canonical ``[VC,*]`` sharding: dim 0 over the whole mesh."""
    axes = (
        mesh.axis_names[0]
        if len(mesh.axis_names) == 1
        else tuple(mesh.axis_names)
    )
    return NamedSharding(mesh, P(axes, *([None] * (ndim - 1))))


def shard_rows(x, mesh: Mesh):
    """Distribute dim 0 over the whole mesh (≙ ``[VC,*]``)."""
    return jax.device_put(x, row_sharding(mesh, np.ndim(x)))


def constrain_rows(x, mesh: Mesh):
    """Row-shard a traced value inside jit (``[VC,*]`` constraint).

    Uses ``with_sharding_constraint`` for Auto-axis meshes and
    ``jax.sharding.reshard`` for Explicit-axis ones (JAX rejects
    constraints on explicit axes)."""
    s = row_sharding(mesh, np.ndim(x))
    if any(
        t == jax.sharding.AxisType.Explicit
        for t in mesh.axis_types
    ):
        return jax.sharding.reshard(x, s)
    return jax.lax.with_sharding_constraint(x, s)


def shard_cols(x, mesh: Mesh):
    """Distribute the last dim over the whole mesh (≙ ``[*,VR]``)."""
    spec = [None] * (np.ndim(x) - 1)
    if len(mesh.axis_names) == 1:
        spec.append(mesh.axis_names[0])
    else:
        spec.append(tuple(mesh.axis_names))
    return shard(x, mesh, *spec)


def replicate(x, mesh: Mesh):
    """Fully replicate (≙ ``[*,*]``)."""
    return shard(x, mesh)


def shard_rows_padded(x, mesh: Mesh, pad_value=0.0):
    """``shard_rows`` for arbitrary row counts: zero-pads dim 0 up to a
    multiple of the mesh size.  Returns ``(sharded, n_orig)`` — callers
    whose math tolerates zero rows (least squares residuals, SVD) trim
    row-shaped outputs back to ``n_orig``."""
    n = x.shape[0]
    total = math.prod(mesh.shape.values())
    pad = (-n) % total
    if pad:
        widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
        import jax.numpy as jnp

        x = jnp.pad(x, widths, constant_values=pad_value)
    return shard_rows(x, mesh), n


def fully_replicated(x):
    """Reshard ``x`` to fully-replicated if it carries an explicit sharding.

    Trace-time safe: under jit with JAX's explicit-sharding types, ops like
    ``qr``/``svd``/``eigh`` reject sharded non-batch dims; small matrices
    (≙ the reference's rank-replicated ``[*,*]`` factorizations) are
    resharded here.  No-op for unsharded/replicated inputs.
    """
    aval = getattr(x, "aval", x)
    sh = getattr(aval, "sharding", None)
    spec = getattr(sh, "spec", None)
    if spec is None or not any(s is not None for s in spec):
        return x
    return jax.sharding.reshard(
        x, NamedSharding(sh.mesh, P(*([None] * np.ndim(x))))
    )
