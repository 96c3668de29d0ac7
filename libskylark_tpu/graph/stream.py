"""Streamed graph sketching: adjacency folds over edge blocks.

The graph layer re-founded on the streaming + sparse substrate (PR-5
engine, PR-9 elastic worlds, PR-12 sharded COO schedules): work scales
with edges *streamed*, not adjacency *held*.  Three routes share one
bitwise contract:

- :func:`streamed_adjacency_sketch` folds COO edge blocks (from
  ``io.stream_arc_list`` or :func:`graph_block_source`) into ``S·A``
  through the per-hash ``jax.ops.segment_sum`` scatter the in-core BCOO
  apply uses.
- :func:`incore_adjacency_sketch` is the reference:
  ``S.apply(A_bcoo, dense_output=True)``.
- :func:`chained_adjacency_sketch` composes ``S₂·(S₁·A)`` either
  on-device through the sharded sparse-out schedule
  (``columnwise_sharded_sparse_out`` → ``ShardedBCOO.sketch_columnwise``)
  or by sketching the streamed fold.

**Why streamed ≡ in-core is bitwise, not approximate**: an unweighted
adjacency has 0/1 entries and hash-sketch values are ±1 (CWT) or ±2⁻¹
(SJLT, nnz=4) — every partial sum is an exact dyadic rational far below
2⁵³, so IEEE-754 addition is exact and the fold is order-invariant.
Block boundaries, batch sizes, rank partitions, and summation schedules
cannot change a single bit.  (Weighted graphs would lose this; the graph
layer is unweighted.)

:func:`streaming_ase` rebuilds ``approximate_ase`` as a ONE-PASS
streaming randomized symmetric eigensolve (Nyström): the only touch of
``A`` is the streamed fold ``SA = Ω·A``; the core ``Ω·A·Ωᵀ`` and the
whitened small eigenproblems are deterministic replicated (s, s)/(n, s)
math.  Exact for exactly-low-rank adjacencies once ``s ≥ rank`` (the
oversampled default), Nyström-approximate otherwise.  Elastic worlds
fold per-rank edge partitions via ``elastic_run_stream`` and merge with
one ``cross_host_psum`` — repartition-on-resume comes with the engine.
"""

from __future__ import annotations

import numpy as np

from ..utils.exceptions import InvalidParameters

__all__ = [
    "graph_block_source",
    "adjacency_sketch_fold",
    "incore_adjacency_sketch",
    "streamed_adjacency_sketch",
    "chained_adjacency_sketch",
    "ase_from_sketch",
    "streaming_ase",
]


def graph_block_source(G, batch_edges: int = 65536, dtype=np.float64):
    """Checkpointable block factory over an in-core graph's edges.

    Yields the same ``{"rows", "cols", "vals"}`` symmetrized COO blocks
    as ``io.stream_arc_list`` — here in canonical CSR (sorted) edge
    order rather than file order; the folds are order-invariant (module
    docstring) so both sources produce bit-identical sketches.
    """
    rows_full = np.repeat(np.arange(G.n, dtype=np.int64), G.degrees)
    upper = rows_full < G.indices
    lo = rows_full[upper]
    hi = G.indices[upper].astype(np.int64)

    def factory(start_batch: int = 0):
        for b0 in range(start_batch * batch_edges, lo.size, batch_edges):
            l, h = lo[b0 : b0 + batch_edges], hi[b0 : b0 + batch_edges]
            yield {
                "rows": np.concatenate([l, h]),
                "cols": np.concatenate([h, l]),
                "vals": np.ones(2 * l.size, dtype=dtype),
            }

    return factory


def adjacency_sketch_fold(S, ncols: int, dtype=np.float64):
    """(init_at, step) for folding edge blocks into columnwise ``S·A``.

    ``step`` scatters each block's entries through the per-hash
    ``segment_sum`` keyed by ``bucket·ncols + col`` — entry-for-entry
    the kernel of the in-core BCOO dense-out apply, addressed by GLOBAL
    vertex ids (edge partitions need no row offsets: the scatter key is
    position-independent, unlike the row-window folds of
    ``distributed_sketch``).  The accumulator's ``"edge"`` leaf counts
    folded undirected edges for the partition end-check.
    """
    import jax
    import jax.numpy as jnp

    from ..sketch.hash import HashSketch

    if not isinstance(S, HashSketch):
        raise InvalidParameters(
            f"graph sketch folds need a hash sketch (CWT/SJLT), got "
            f"{type(S).__name__}"
        )
    jdt = jnp.dtype(dtype)
    # Hoist the full bucket/value windows once (O(nnz·n) — the vertex
    # set fits by contract; the edge file need not).
    bs = [S.buckets(h * S.n, S.n) for h in range(S.nnz)]
    vs = [S.values(jdt, h * S.n, S.n) for h in range(S.nnz)]

    def init_at(edge0: int):
        return {
            "sa": jnp.zeros((S.s, int(ncols)), jdt),
            "edge": np.asarray(edge0, np.int64),
        }

    def step(acc, block, index):
        rows = jnp.asarray(block["rows"]).astype(jnp.int32)
        cols = jnp.asarray(block["cols"]).astype(jnp.int32)
        vals = jnp.asarray(block["vals"]).astype(jdt)
        sa = acc["sa"]
        for h in range(S.nnz):
            key = bs[h][rows] * jnp.int32(ncols) + cols
            sa = sa + jax.ops.segment_sum(
                vals * vs[h][rows], key, num_segments=S.s * int(ncols)
            ).reshape(S.s, int(ncols))
        folded = int(block["rows"].shape[0]) // 2
        return {
            "sa": sa,
            "edge": np.asarray(int(acc["edge"]) + folded, np.int64),
        }

    return init_at, step


def incore_adjacency_sketch(G, S, dtype=None):
    """The bitwise reference: ``S.apply(A_bcoo, dense_output=True)``.

    ``G`` may be a ``SimpleGraph`` or a BCOO adjacency.
    """
    from jax.experimental import sparse as jsparse

    from .graph import SimpleGraph

    A = G.adjacency_bcoo(dtype) if isinstance(G, SimpleGraph) else G
    if not isinstance(A, jsparse.BCOO):
        raise InvalidParameters(
            f"incore_adjacency_sketch needs a SimpleGraph or BCOO "
            f"adjacency, got {type(G).__name__}"
        )
    return S.apply(A, "columnwise", dense_output=True)


def streamed_adjacency_sketch(
    source,
    S,
    *,
    ncols: int,
    dtype=np.float64,
    partition=None,
    params=None,
    fault_plan=None,
    epoch: int = 0,
):
    """One-pass columnwise ``S·A`` over an edge-block stream.

    ``source``: a block factory (``io.arc_list_source``,
    :func:`graph_block_source`) or iterable of edge blocks.  With
    ``partition=None`` this is the single-process resilient fold
    (checkpoint/resume via ``StreamParams``); with an edge
    :class:`~libskylark_tpu.streaming.elastic.RowPartition`
    (``nrows`` = unique undirected edges) every process of a real
    ``jax.distributed`` world folds its edge share and partials merge
    with one psum — simulated ranks drive ``elastic_run_stream`` +
    :func:`adjacency_sketch_fold` directly and merge explicitly.
    Bit-identical to :func:`incore_adjacency_sketch` in every
    configuration (module docstring).
    """
    import jax.numpy as jnp

    from .. import guard
    from ..sketch.base import Dimension

    init_at, step = adjacency_sketch_fold(S, ncols, dtype)
    kind = "graph_streaming_sketch"
    report = guard.RecoveryReport(stage=kind)

    if partition is None:
        from ..streaming.engine import StreamParams, run_stream

        params = params or StreamParams()
        acc, _ = run_stream(
            source, step, init_at(0), params,
            kind=kind, fault_plan=fault_plan, report=report,
        )
        partial = acc["sa"]
        merged = partial
    else:
        from ..parallel.collectives import cross_host_psum
        from ..streaming.elastic import (
            ElasticParams,
            _make_watchdog,
            _require_real_world,
            _resolve_world,
            elastic_run_stream,
        )

        _require_real_world(partition)
        params = params or ElasticParams()
        rank, world = _resolve_world(params)
        partition.validate_world(rank, world)
        e0, e1 = partition.row_range(rank)
        kind = "graph_distributed_sketch"
        acc, _ = elastic_run_stream(
            source, step, init_at(e0), partition, params,
            kind=kind, fault_plan=fault_plan, report=report, epoch=epoch,
        )
        edges = int(acc["edge"])
        if edges != e1:
            raise ValueError(
                f"rank {rank} folded edges [{e0}, {edges}) but its "
                f"partition share is [{e0}, {e1}); the source and "
                "partition disagree"
            )
        watchdog = (
            _make_watchdog(params, params.checkpoint_dir, rank, world, epoch)
            if params.checkpoint_dir
            else None
        )
        merged = cross_host_psum({"sa": acc["sa"]}, watchdog=watchdog)["sa"]
    out = S.finalize_slices(jnp.asarray(merged), Dimension.COLUMNWISE)
    if guard.enabled():
        guard.check_finite(out, kind, report=report)
    return out


def chained_adjacency_sketch(
    G,
    S1,
    S2,
    *,
    mesh=None,
    streamed: bool = False,
    batch_edges: int = 65536,
    dtype=None,
):
    """``S₂·(S₁·A)`` without materializing the intermediate off-device.

    In-core (default): the BCOO adjacency rides
    ``columnwise_sharded_sparse_out`` — ``S₁·A`` lands ROW-BLOCK-SHARDED
    and ``ShardedBCOO.sketch_columnwise`` hashes it in place (one psum,
    no host exit, no densified intermediate).  ``streamed=True`` folds
    ``S₁·A`` from edge blocks first, then applies ``S₂`` — same bits,
    by the exactness argument in the module docstring.  Requires
    ``S2.n == S1.s``.
    """
    from .graph import SimpleGraph

    if S2.n != S1.s:
        raise InvalidParameters(
            f"chained sketch needs S2.n == S1.s, got S2.n={S2.n}, "
            f"S1.s={S1.s}"
        )
    if streamed:
        ddt = np.float64 if dtype is None else dtype
        SA1 = streamed_adjacency_sketch(
            graph_block_source(G, batch_edges=batch_edges, dtype=ddt),
            S1, ncols=G.n, dtype=ddt,
        )
        return S2.apply(SA1, "columnwise")
    from ..parallel.collectives import columnwise_sharded_sparse_out

    if not isinstance(G, SimpleGraph):
        raise InvalidParameters(
            "chained_adjacency_sketch needs a SimpleGraph"
        )
    if mesh is None:
        # 1-D mesh over all visible devices.
        import jax
        from jax.sharding import Mesh

        from ..parallel.mesh import ROWS

        mesh = Mesh(np.array(jax.devices()), (ROWS,))
    sharded = columnwise_sharded_sparse_out(S1, G.adjacency_bcoo(dtype), mesh)
    return sharded.sketch_columnwise(S2, dense_output=True)


def ase_from_sketch(SA, S, k: int):
    """Nyström symmetric eigensolve from the one-pass sketch ``SA = Ω·A``.

    With ``Y = AΩᵀ = SAᵀ`` and core ``C = ΩAΩᵀ`` (one more sketch apply
    — no second pass over ``A``), ``A ≈ Y C⁺ Yᵀ``; whitening ``Y`` by
    ``C``'s floored inverse-sqrt and orthogonalizing through Gram
    eigensolves (the ``gram_orth`` floor discipline of ``linalg/svd.py``)
    turns that into an eigendecomposition.  Signed: ``C``'s negative
    eigenvalues carry through, so bipartite-like spectra (λ < 0) are
    recovered — exact when ``rank(A) ≤ s``.  All (s, s) math is
    replicated and deterministic: every rank computes identical bits
    from the merged ``SA``.  Returns ``(V, lam)``, top-k by |λ|.
    """
    import jax
    import jax.numpy as jnp

    from ..parallel.mesh import fully_replicated

    dtype = SA.dtype
    s = SA.shape[0]
    Y = SA.T  # (n, s) = A·Ωᵀ (A symmetric)
    C = S.apply(Y, "columnwise")  # (s, s) = Ω·A·Ωᵀ
    C = fully_replicated((C + C.T) / 2)
    c, Uc = jnp.linalg.eigh(C)
    abs_c = jnp.abs(c)
    eps = jnp.finfo(dtype).eps
    floor = jnp.max(abs_c) * eps * s
    cscale = jnp.where(
        abs_c > floor, jax.lax.rsqrt(jnp.maximum(abs_c, floor)),
        jnp.zeros((), dtype),
    )
    sgn = jnp.where(abs_c > floor, jnp.sign(c), jnp.zeros((), dtype))
    M = jnp.dot(Y, Uc * cscale[None, :], precision="highest")
    Gm = fully_replicated(jnp.dot(M.T, M, precision="highest"))
    g, Vg = jnp.linalg.eigh(Gm)
    gfloor = jnp.maximum(g[-1], 0) * eps * s
    gscale = jnp.where(
        g > gfloor, jax.lax.rsqrt(jnp.maximum(g, gfloor)),
        jnp.zeros((), dtype),
    )
    Q = jnp.dot(M, Vg * gscale[None, :], precision="highest")  # M ≈ Q·R
    R = jnp.sqrt(jnp.maximum(g, 0))[:, None] * Vg.T
    T = jnp.dot(R * sgn[None, :], R.T, precision="highest")
    T = fully_replicated((T + T.T) / 2)
    lam, W = jnp.linalg.eigh(T)
    order = jnp.argsort(-jnp.abs(lam))[:k]
    V = jnp.dot(Q, W, precision="highest")[:, order]
    return V, lam[order]


def streaming_ase(
    source,
    n: int,
    k: int,
    context,
    params=None,
    *,
    dtype=np.float64,
    partition=None,
    fault_plan=None,
    epoch: int = 0,
):
    """Streaming randomized ASE: ``(X, lam)`` from ONE pass over edges.

    The only O(edges) work is the streamed fold ``SA = Ω·A`` (SJLT Ω,
    oversampled width from the shared ``_sketch_size`` sizing); the
    embedding follows from :func:`ase_from_sketch`'s replicated small
    math, ``X = V·√|λ|``.  One-pass by construction — subspace
    iteration would need re-streaming, so ``num_iterations > 0`` is
    rejected; use the in-core route for polished spectra of graphs that
    fit.
    """
    import jax.numpy as jnp

    from ..linalg.svd import SVDParams, _sketch_size
    from ..sketch.hash import SJLT

    params = params or SVDParams()
    if getattr(params, "num_iterations", 0):
        raise InvalidParameters(
            "streaming ASE is one-pass: subspace iteration "
            f"(num_iterations={params.num_iterations}) would re-stream "
            "the edges; use the in-core route or num_iterations=0"
        )
    k, s = _sketch_size(k, params, n)
    S = SJLT(n, s, context)
    SA = streamed_adjacency_sketch(
        source, S, ncols=n, dtype=dtype,
        partition=partition, fault_plan=fault_plan, epoch=epoch,
    )
    V, lam = ase_from_sketch(SA, S, k)
    X = V * jnp.sqrt(jnp.abs(lam))[None, :]
    return X, lam
