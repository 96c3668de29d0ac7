"""Approximate adjacency spectral embedding (ASE).

≙ ``ApproximateASE`` (``ml/graph/spectral_embedding.hpp:19-94``, Lyzinski
et al): randomized symmetric SVD of the adjacency matrix, embeddings
``X = V·diag(√|λ|)``.  The SVD is the TPU-heavy part and reuses
``approximate_symmetric_svd`` (sharded subspace iteration): its three
cached programs run under the stages ``ase.sketch``, ``ase.power`` and
``ase.ritz``, the scaling under ``ase.embed``, all inside the entry span
``approximate_ase``.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import telemetry
from ..core.context import SketchContext
from ..core.sparse import prepare
from ..linalg.svd import SVDParams, approximate_symmetric_svd
from ..utils import profiling
from .graph import SimpleGraph

__all__ = ["ASEParams", "approximate_ase"]


@dataclass
class ASEParams(SVDParams):
    """≙ ``approximate_ase_params_t`` (inherits the SVD oversampling/
    iteration knobs).

    ``streamed=True`` routes a ``SimpleGraph`` through the one-pass
    streaming eigensolve (``graph.stream.streaming_ase``): the adjacency
    is never materialized — edge blocks of ``batch_edges`` undirected
    edges fold into ``Ω·A`` and the embedding follows from replicated
    small math.  One-pass, so it requires ``num_iterations == 0``.
    """

    sparse: bool = False  # use BCOO adjacency
    streamed: bool = False  # fold edge blocks; never build A
    batch_edges: int = 65536  # undirected edges per streamed block


@jax.jit
def _embed(V, lam):
    return V * jnp.sqrt(jnp.abs(lam))[None, :]


def approximate_ase(
    G,
    k: int,
    context: SketchContext,
    params: ASEParams | None = None,
    *,
    return_info: bool = False,
):
    """Returns (X, lam): X (n, k) embeddings, lam the eigenvalues.

    ``G`` may be a ``SimpleGraph`` or an (n, n) adjacency: an array, a
    BCOO (``graph.adjacency_from_edges`` builds one on the device from
    integer edge arrays) or, to pay for the sparse product's layout once
    and not in every call, that BCOO prepared
    (``core.sparse.prepare(A, symmetric=True)``).  ``return_info=True``
    returns ``((X, lam), info)`` with ``approximate_symmetric_svd``'s
    counts (``products``, ``iterations``, ``nnz``, ``edge_chunks``, and
    ``tables`` and ``hot_share`` where the prepared operand gathers from
    a hot table); the streamed route has none to give.
    """
    params = params or ASEParams()
    if isinstance(G, SimpleGraph) and params.streamed:
        from .stream import graph_block_source, streaming_ase

        out = streaming_ase(
            graph_block_source(G, batch_edges=params.batch_edges),
            G.n, k, context, params,
        )
        return (out, {}) if return_info else out
    if isinstance(G, SimpleGraph) and params.sparse:
        # the product's layout is built here, where the adjacency is made
        A = prepare(G.adjacency_bcoo(), symmetric=True)
    elif isinstance(G, SimpleGraph):
        A = jnp.asarray(G.adjacency())
    else:
        A = G
    with telemetry.span("approximate_ase"):
        (V, lam), info = approximate_symmetric_svd(
            A, k, context, params, return_info=True, stage="ase"
        )
        with telemetry.span("ase.embed"):
            X = profiling.launch(_embed, V, lam)
    return ((X, lam), info) if return_info else (X, lam)
