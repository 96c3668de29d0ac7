"""Explicit shard_map sketch-apply schedules.

Most of the framework lets GSPMD choose communication (SURVEY §2.7 P4).
This module keeps the two schedules the reference treats as *invariants*
explicit, as `shard_map` programs:

- ``rowwise_sharded``: A sharded over rows (``[VC,*]``), sketch along the
  replicated feature axis — **communication-free** by construction
  (≙ ``doc/sphinx/sketching.rst:104-118``; the sketch operand is realized
  shard-locally from the counter stream, P5, and no collective is ever
  emitted — guaranteed here rather than hoped from the partitioner).
- ``columnwise_sharded``: A sharded over rows, sketched *along* the
  sharded axis: each shard sketches its row block with its own counter
  window of Omega, then one ``psum`` (or ``psum_scatter``) combines —
  the reduce-scatter schedule of
  ``sketch/dense_transform_Elemental_mc_mr.hpp:179,302,599``.

Works for any transform whose apply is local given the right counter
window; dense transforms expose that through ``realize`` (which accepts
traced, shard-dependent offsets), hash transforms through per-coordinate
``buckets``/``values`` slices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import sparse as jsparse
from jax.sharding import Mesh, PartitionSpec as P

from ..sketch.base import Dimension
from ..sketch.dense import DenseSketch

__all__ = [
    "cross_host_psum",
    "CollectiveWatchdog",
    "HEARTBEAT_DIR",
    "rowwise_sharded",
    "columnwise_sharded",
    "batch_sharded_program",
    "columnwise_batch_sharded",
    "rowwise_sharded_sparse",
    "columnwise_sharded_sparse",
    "columnwise_sharded_sparse_2d",
    "columnwise_sharded_sparse_out",
    "columnwise_sharded_sparse_out_2d",
    "rowwise_sharded_sparse_out",
    "suggest_sparse_out_capacity",
    "ShardedBCOO",
]


HEARTBEAT_DIR = "heartbeats"


class CollectiveWatchdog:
    """Deadline-bound a blocking collective instead of hanging forever.

    The failure mode PR 6 left open: a peer dies (or wedges) between its
    last fold and the merge, and every survivor blocks inside
    ``process_allgather`` / ``psum`` with no timeout — the MPI-era hang
    the reference accepted.  The watchdog runs the collective on a
    worker thread and polls from the caller's thread:

    - **heartbeats**: before entering a phase, each rank atomically
      writes ``<root>/heartbeats/rank-<r>.json`` (``{rank, epoch, phase,
      ts}``).  On timeout the survivor reads its peers' files and names
      the ranks whose heartbeat never reached the phase — evidence for
      the orchestrator, not just "it hung".
    - **deadline**: past ``deadline_s`` a typed
      :class:`~libskylark_tpu.utils.exceptions.CollectiveTimeoutError`
      (code 110) is raised with the straggler list.
    - **epoch fencing**: a peer heartbeat carrying a HIGHER epoch means
      the world repartitioned without us — raise
      :class:`~libskylark_tpu.utils.exceptions.StaleEpochError` (111)
      immediately rather than waiting out the deadline.

    ``deadline_s=None`` (the default, env-overridable with
    ``SKYLARK_COLLECTIVE_TIMEOUT_S``) disables the worker thread
    entirely: the collective runs inline, bit-for-bit the pre-watchdog
    behavior.  Single-process worlds never build one.
    """

    def __init__(
        self,
        root=None,
        *,
        rank: int = 0,
        world: int = 1,
        epoch: int = 0,
        deadline_s: float | None = None,
        poll_s: float = 0.25,
    ):
        import os

        if deadline_s is None:
            env = os.environ.get("SKYLARK_COLLECTIVE_TIMEOUT_S")
            if env:
                try:
                    deadline_s = float(env)
                except ValueError:
                    deadline_s = None
        self.dir = (
            os.path.join(str(root), HEARTBEAT_DIR) if root else None
        )
        self.rank = int(rank)
        self.world = int(world)
        self.epoch = int(epoch)
        self.deadline_s = deadline_s
        self.poll_s = float(poll_s)

    def _path(self, rank: int) -> str:
        import os

        return os.path.join(self.dir, f"rank-{int(rank):05d}.json")

    def beat(self, phase: str) -> None:
        """Announce arrival at ``phase`` (atomic write, best-effort: a
        full disk must not turn a healthy collective into a failure)."""
        import json
        import os
        import time

        if self.dir is None:
            return
        try:
            os.makedirs(self.dir, exist_ok=True)
            payload = json.dumps(
                {
                    "rank": self.rank,
                    "epoch": self.epoch,
                    "phase": str(phase),
                    "ts": round(time.time(), 6),
                }
            )
            tmp = self._path(self.rank) + f".tmp.{os.getpid()}"
            with open(tmp, "w", encoding="utf-8") as fh:
                fh.write(payload)
            os.replace(tmp, self._path(self.rank))
        except OSError:
            pass

    def peers(self) -> dict:
        """``{rank: heartbeat dict}`` for every readable peer file."""
        import json
        import os

        out = {}
        if self.dir is None:
            return out
        try:
            names = os.listdir(self.dir)
        except OSError:
            return out
        for name in sorted(names):
            if not (name.startswith("rank-") and name.endswith(".json")):
                continue
            try:
                with open(
                    os.path.join(self.dir, name), encoding="utf-8"
                ) as fh:
                    rec = json.load(fh)
                out[int(rec["rank"])] = rec
            except (OSError, json.JSONDecodeError, KeyError, ValueError):
                continue
        return out

    def _check_stale(self) -> None:
        from ..utils.exceptions import StaleEpochError

        for rank, rec in self.peers().items():
            if int(rec.get("epoch", 0)) > self.epoch:
                raise StaleEpochError(
                    f"rank {self.rank} runs at elastic epoch "
                    f"{self.epoch} but rank {rank}'s heartbeat announces "
                    f"epoch {rec.get('epoch')}: the world repartitioned "
                    "past this process — its partials are stale",
                    expected=self.epoch,
                    got=int(rec.get("epoch", 0)),
                )

    def stragglers(self, phase: str) -> list:
        """Ranks whose heartbeat never reached ``phase`` (best-effort:
        empty when no heartbeat root is configured)."""
        if self.dir is None:
            return []
        seen = self.peers()
        return [
            r
            for r in range(self.world)
            if r != self.rank
            and (r not in seen or seen[r].get("phase") != str(phase))
        ]

    def guard(self, phase: str, fn):
        """Run ``fn()`` (a blocking collective) bounded by the deadline.

        Inline (no thread, no overhead) when no deadline is configured.
        """
        import threading
        import time

        from .. import telemetry
        from ..utils.exceptions import CollectiveTimeoutError

        self.beat(phase)
        if not self.deadline_s or self.deadline_s <= 0:
            return fn()
        box = {}
        done = threading.Event()

        def _run():
            try:
                box["result"] = fn()
            except BaseException as e:  # noqa: BLE001 — re-raised below
                box["error"] = e
            finally:
                done.set()

        worker = threading.Thread(
            target=_run, name=f"collective-{phase}", daemon=True
        )
        worker.start()
        deadline = time.monotonic() + float(self.deadline_s)
        while not done.wait(timeout=min(self.poll_s, 0.25)):
            self._check_stale()
            if time.monotonic() >= deadline:
                stragglers = self.stragglers(phase)
                if telemetry.enabled():
                    telemetry.inc("collective.timeouts")
                    telemetry.event(
                        "collective", "timeout",
                        {
                            "phase": str(phase),
                            "rank": self.rank,
                            "world": self.world,
                            "epoch": self.epoch,
                            "deadline_s": float(self.deadline_s),
                            "stragglers": stragglers,
                        },
                    )
                who = (
                    str(stragglers)
                    if stragglers
                    else "unknown (no heartbeat root)"
                )
                raise CollectiveTimeoutError(
                    f"collective {phase!r} did not complete within "
                    f"{self.deadline_s}s on rank {self.rank} (world "
                    f"{self.world}, epoch {self.epoch}); ranks that "
                    f"never arrived: {who}",
                    phase=str(phase),
                    deadline_s=float(self.deadline_s),
                    stragglers=stragglers,
                )
        if "error" in box:
            raise box["error"]
        return box.get("result")


def _coerce_float(A):
    A = jnp.asarray(A)
    if not jnp.issubdtype(A.dtype, jnp.floating):
        A = A.astype(jnp.float32)
    return A


def cross_host_psum(
    tree,
    mesh: Mesh | None = None,
    *,
    watchdog: CollectiveWatchdog | None = None,
    phase: str = "psum",
):
    """Elementwise sum of a host-local float pytree over every process of
    the ``jax.distributed`` world — the merge schedule of the elastic
    streaming engine (each host folds its own row range into a partial
    ``S·A``; columnwise partials merge by sum, so one psum finishes the
    global sketch).

    Layout: each process contributes its value on its FIRST addressable
    device of ``mesh`` (default: the global 1-D device mesh) and zeros on
    the rest, then one ``shard_map`` ``psum`` over the device axis sums
    exactly one copy per process.  The result comes back as host numpy
    arrays, identical on every process.

    Single-process worlds return ``tree`` unchanged — a bitwise no-op,
    so the non-distributed streaming paths keep their PR-5 bit-identity
    even when routed through this merge.

    ``watchdog`` (a :class:`CollectiveWatchdog`) deadline-bounds the
    merge: a peer that never arrives raises ``CollectiveTimeoutError``
    (code 110) with straggler evidence instead of hanging the world.
    ``None`` (the default) keeps the blocking behavior bit-for-bit.
    """
    import numpy as np

    if jax.process_count() == 1:
        return tree
    if watchdog is not None:
        wd, watchdog = watchdog, None
        return wd.guard(
            phase, lambda: cross_host_psum(tree, mesh, watchdog=None)
        )

    import time as _time

    from .. import telemetry as _telemetry

    # Straggler attribution: each rank times its own merge wall (arrive
    # + wait for peers + sum).  The rank that arrived LAST shows the
    # SHORTEST wait — the fleet's per-rank gauges name it.  Timed on
    # the innermost path only, so a watchdog-guarded call counts once.
    t_wait = _time.monotonic() if _telemetry.enabled() else None

    from jax.sharding import NamedSharding

    if mesh is None:
        mesh = Mesh(np.asarray(jax.devices()), ("hosts",))
    axes = tuple(mesh.axis_names)
    mesh_devs = list(mesh.devices.flat)
    nd = len(mesh_devs)
    me = jax.process_index()
    mine = [i for i, d in enumerate(mesh_devs) if d.process_index == me]
    if not mine:
        raise ValueError(
            "cross_host_psum: mesh has no addressable device for process "
            f"{me}"
        )
    first = mine[0]

    leaves, treedef = jax.tree.flatten(tree)
    out = []
    for leaf in leaves:
        x = np.asarray(leaf)
        if not jnp.issubdtype(x.dtype, jnp.floating):
            raise TypeError(
                "cross_host_psum sums floating-point leaves only (merge "
                f"bookkeeping ints locally), got {x.dtype}"
            )
        zeros = np.zeros_like(x)
        spec = P(axes, *([None] * x.ndim))

        def _cb(idx, x=x, zeros=zeros):
            dev = idx[0].start or 0
            return (x if dev == first else zeros)[None]

        g = jax.make_array_from_callback(
            (nd,) + x.shape, NamedSharding(mesh, spec), _cb
        )
        summed = jax.jit(
            jax.shard_map(
                lambda a: jax.lax.psum(a, axes),
                mesh=mesh,
                in_specs=spec,
                out_specs=P(*([None] * (x.ndim + 1))),
            )
        )(g)
        out.append(np.asarray(summed.addressable_data(0))[0])
    result = jax.tree.unflatten(treedef, out)
    if t_wait is not None:
        wait_ms = (_time.monotonic() - t_wait) * 1e3
        _telemetry.observe_phase("collective_wait", wait_ms)
        _telemetry.set_gauge("collective.last_wait_ms", round(wait_ms, 4))
        _telemetry.set_gauge("collective.rank", jax.process_index())
    return result


def rowwise_sharded(S, A, mesh: Mesh):
    """A (m, N) sharded on rows → A·Omegaᵀ (m, S) sharded on rows.

    Zero communication: each shard applies the full sketch to its local
    rows (Omega realized in-shard).
    """
    axes = tuple(mesh.axis_names)
    A = _coerce_float(A)

    def local(a):
        return S.apply(a, Dimension.ROWWISE)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=P(axes, None),
        out_specs=P(axes, None),
    )(A)


def batch_sharded_program(local, mesh: Mesh):
    """Shard the BATCH axis: run ``local`` on column blocks of a 2-D
    operand, outputs re-concatenated on columns.  Communication-free by
    construction — the serving layer's device-parallel dispatch schedule,
    where the columns are independent coalesced requests.

    Contrast :func:`columnwise_sharded`, which shards the CONTRACTION
    axis and merges with a ``psum``: the psum reorders the accumulation,
    so its result is only approximately the single-device one.  Here no
    reduction crosses shards, so the result is bitwise-identical to the
    unsharded ``local`` PROVIDED (a) ``local`` is column-pure (each
    output column depends only on its input column — the per-slot purity
    the serve batcher's coalescing contract already pins) and (b) every
    shard's column block keeps the lane-uniform width the XLA gemm
    micro-kernels key on (a multiple of the serve ladder's base rung; a
    remainder-width shard would take a different accumulation
    micro-kernel and break bit-parity).  Callers gate on (b); this
    schedule just runs.
    """
    axes = tuple(mesh.axis_names)
    # check_vma=False: the sketch applies trace counter-stream
    # primitives that carry no replication rule; nothing here relies on
    # replication inference (every spec is explicit).
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=P(None, axes),
        out_specs=P(None, axes),
        check_vma=False,
    )


def columnwise_batch_sharded(S, B, mesh: Mesh):
    """B (N, k) of k independent RHS columns → S·B (S.s, k), sharded on
    the batch (column) axis: each shard applies the FULL sketch to its
    column block (no counter windowing — every shard sees the whole
    Omega, unlike :func:`columnwise_sharded`'s contraction split).  Zero
    communication, and bitwise-equal to the unsharded columnwise apply
    under :func:`batch_sharded_program`'s lane-uniformity proviso."""
    nshards = mesh.size
    B = _coerce_float(B)
    k = B.shape[1]
    if k % nshards:
        raise ValueError(
            f"batch columns {k} not divisible by mesh size {nshards}"
        )

    def local(b):
        return S.apply(b, Dimension.COLUMNWISE)

    return batch_sharded_program(local, mesh)(B)


def columnwise_sharded(S: DenseSketch, A, mesh: Mesh, scatter: bool = False):
    """A (N, m) sharded on rows → S·A (S, m).

    Each shard multiplies its Omega column window (counter-derived, local
    — ``realize`` with a shard-dependent traced offset) with its row
    block, then a ``psum`` sums partial products; with ``scatter=True`` a
    ``psum_scatter`` leaves the output row-sharded (the reference's
    reduce-scatter within grid columns).
    """
    axes = tuple(mesh.axis_names)
    A = _coerce_float(A)
    nshards = mesh.size
    n = A.shape[0]
    if n % nshards:
        raise ValueError(f"rows {n} not divisible by mesh size {nshards}")
    block = n // nshards
    if S.s % nshards and scatter:
        raise ValueError(f"S={S.s} not divisible by mesh size for scatter")

    def local(a):
        idx = jax.lax.axis_index(axes)  # linearized shard index
        omega_blk = S.realize(
            a.dtype, offset=(0, idx * block), shape=(S.s, block)
        )
        partial_out = omega_blk @ a  # (S, m_local) partial product
        if scatter:
            return jax.lax.psum_scatter(
                partial_out, axes, scatter_dimension=0, tiled=True
            )
        return jax.lax.psum(partial_out, axes)

    out_spec = P(axes, None) if scatter else P(None, None)
    return jax.shard_map(
        local, mesh=mesh, in_specs=P(axes, None), out_specs=out_spec
    )(A)


# ---------------------------------------------------------------------------
# P6: explicit sharded SPARSE hash-sketch schedules.
#
# The reference distributes sparse matrices on a CombBLAS √p×√p grid and
# applies hash sketches block-locally, merging with an MPI reduce
# (``sketch/hash_transform_CombBLAS.hpp:136-302``); its own docs call 2-D
# sparse layouts imbalanced for 1-D data (``base/sparse_dist_matrix.hpp:37-41``).
# The TPU re-design shards COO nonzeros by row block (balanced padding),
# computes each shard's bucket/value counter window in-shard (P5: no sketch
# data on the wire), scatter-adds into a dense (S, m) accumulator — sketch
# outputs are short-and-dense by design, the mixed sparse→dense path of
# ``hash_transform_Mixed.hpp`` — and merges with one psum (or psum_scatter,
# the ragged-all-to-all stand-in that keeps the output sharded).


def _shard_coo_rows(A, nshards: int, block: int):
    """Host-side: split BCOO nonzeros into row blocks, padding each block
    to equal nnz with zero-data entries (they scatter 0 — harmless)."""
    import numpy as np

    rows = np.asarray(A.indices[:, 0])
    cols = np.asarray(A.indices[:, 1])
    data = np.asarray(A.data)
    owner = rows // block
    counts = np.bincount(owner, minlength=nshards)
    max_nnz = max(1, int(counts.max()))
    d = np.zeros((nshards, max_nnz), data.dtype)
    lr = np.zeros((nshards, max_nnz), np.int32)
    cc = np.zeros((nshards, max_nnz), np.int32)
    for p in range(nshards):
        sel = owner == p
        k = int(counts[p])
        d[p, :k] = data[sel]
        lr[p, :k] = rows[sel] - p * block
        cc[p, :k] = cols[sel]
    return jnp.asarray(d), jnp.asarray(lr), jnp.asarray(cc)


def _coo_dtype(data):
    return (
        data.dtype
        if jnp.issubdtype(data.dtype, jnp.floating)
        else jnp.float32
    )


def columnwise_sharded_sparse(S, A, mesh: Mesh, scatter: bool = False):
    """BCOO A (N, m), nonzeros owned by row block → dense S·A (S, m).

    Each shard hashes its row block with its own bucket/value counter
    windows (contiguous in the (nnz, N) flat layout, so shard-local) and
    scatter-adds into a local (S, m) accumulator; one ``psum`` merges
    (``psum_scatter`` with ``scatter=True`` leaves rows sharded).
    """
    axes = tuple(mesh.axis_names)
    p = mesh.size
    n, m = A.shape
    if n != S.n:
        raise ValueError(f"columnwise apply needs A with {S.n} rows, got {A.shape}")
    if n % p:
        raise ValueError(f"rows {n} not divisible by mesh size {p}")
    if scatter and S.s % p:
        raise ValueError(f"S={S.s} not divisible by mesh size for scatter")
    block = n // p
    d, lr, cc = _shard_coo_rows(A, p, block)
    if n >= (1 << 32):
        # Traced shard offsets ride raw_bits' uint32 lane; the static
        # h·N part of the window start is folded into the 64-bit counter
        # base below, so only N itself must stay below 2^32.
        raise ValueError(
            f"columnwise_sharded_sparse supports N < 2^32, got N={n}"
        )
    return _columnwise_sparse_program(S, m, block, mesh, scatter)(d, lr, cc)


def _columnwise_sparse_program(S, m: int, block: int, mesh: Mesh,
                               scatter: bool):
    """The jittable device half of :func:`columnwise_sharded_sparse`
    (host-side COO row-block splitting already done).  Factored out so the
    compiled-HLO schedule tests can lower exactly the program that runs."""
    axes = tuple(mesh.axis_names)

    def local(d, lr, cc):
        dtype = _coo_dtype(d)
        d, lr, cc = d[0].astype(dtype), lr[0], cc[0]
        idx = jax.lax.axis_index(axes)
        acc = jnp.zeros((S.s * m,), dtype)
        # uint32 shard offset + static h·N base: exact for any nnz·N
        # (an int32 product here would wrap at 2^31 and silently select
        # wrong counter windows).
        off = jnp.uint32(idx) * jnp.uint32(block)
        for h in range(S.nnz):
            start = (h * S.n, off)
            b = S.buckets(start=start, num=block)  # (block,) in-shard
            v = S.values(dtype, start=start, num=block)
            acc = acc + jax.ops.segment_sum(
                d * v[lr], b[lr] * m + cc, num_segments=S.s * m
            )
        out = acc.reshape(S.s, m)
        if scatter:
            return jax.lax.psum_scatter(
                out, axes, scatter_dimension=0, tiled=True
            )
        return jax.lax.psum(out, axes)

    out_spec = P(axes, None) if scatter else P(None, None)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axes, None), P(axes, None), P(axes, None)),
        out_specs=out_spec,
    )


def _shard_coo_grid(A, pr: int, pc: int, rblock: int, cblock: int):
    """Host-side: split BCOO nonzeros onto a (pr, pc) grid by
    (row-block, col-block) ownership, padding every cell to equal nnz
    with zero-data entries (they scatter 0 — harmless)."""
    import numpy as np

    rows = np.asarray(A.indices[:, 0])
    cols = np.asarray(A.indices[:, 1])
    data = np.asarray(A.data)
    oi = rows // rblock
    oj = cols // cblock
    owner = oi * pc + oj
    counts = np.bincount(owner, minlength=pr * pc)
    max_nnz = max(1, int(counts.max()))
    d = np.zeros((pr, pc, max_nnz), data.dtype)
    lr = np.zeros((pr, pc, max_nnz), np.int32)
    lc = np.zeros((pr, pc, max_nnz), np.int32)
    for p in range(pr * pc):
        i, j = divmod(p, pc)
        sel = owner == p
        k = int(counts[p])
        d[i, j, :k] = data[sel]
        lr[i, j, :k] = rows[sel] - i * rblock
        lc[i, j, :k] = cols[sel] - j * cblock
    return jnp.asarray(d), jnp.asarray(lr), jnp.asarray(lc)


def _validate_grid_2d(S, A, mesh: Mesh, fn_name: str):
    """Shared preamble of the 2-D grid schedules: axis/shape/2^32
    validation + host-side COO grid split.  Returns
    ``(pr, pc, rblock, cblock, d, lr, lc)``."""
    if len(mesh.axis_names) != 2:
        raise ValueError(
            f"{fn_name} needs a 2-axis mesh, got {mesh.axis_names}"
        )
    ax_r, ax_c = mesh.axis_names
    pr, pc = mesh.shape[ax_r], mesh.shape[ax_c]
    n, m = A.shape
    if n != S.n:
        raise ValueError(f"columnwise apply needs A with {S.n} rows, got {A.shape}")
    if n % pr or m % pc:
        raise ValueError(
            f"shape {A.shape} not divisible by mesh grid ({pr}, {pc})"
        )
    if n >= (1 << 32):
        raise ValueError(f"supports N < 2^32, got N={n}")
    rblock, cblock = n // pr, m // pc
    d, lr, lc = _shard_coo_grid(A, pr, pc, rblock, cblock)
    return pr, pc, rblock, cblock, d, lr, lc


def columnwise_sharded_sparse_2d(S, A, mesh: Mesh):
    """BCOO A (N, m) on a 2-D grid → dense S·A (S, m), column-sharded.

    The 2-D answer to ``sketch/hash_transform_CombBLAS.hpp:136-302``'s
    √p×√p distribution, for matrices long in BOTH dimensions (where the
    1-D row-block schedule's (S, m) accumulator or per-shard column span
    would not fit): nonzeros are owned by (row-block, column-block); each
    shard hashes its row window with in-shard counter windows (P5) and
    scatter-adds a LOCAL (S, m/pc) block; one ``psum`` over the mesh ROW
    axis merges partial products, leaving the output sharded over mesh
    columns — communication ∝ S·m/pc per shard, never the nonzeros.

    Needs a 2-axis mesh (e.g. ``make_mesh((pr, pc))``); N and m must
    divide the respective axis sizes.
    """
    _, _, rblock, cblock, d, lr, lc = _validate_grid_2d(
        S, A, mesh, "columnwise_sharded_sparse_2d"
    )
    return _columnwise_sparse_2d_program(S, rblock, cblock, mesh)(d, lr, lc)


def _columnwise_sparse_2d_program(S, rblock: int, cblock: int, mesh: Mesh):
    """Jittable device half of :func:`columnwise_sharded_sparse_2d`
    (host-side grid split done); factored out for the compiled-HLO
    schedule tests."""
    ax_r, ax_c = mesh.axis_names

    def local(d, lr, lc):
        d, lr, lc = d[0, 0], lr[0, 0], lc[0, 0]
        dtype = _coo_dtype(d)
        d = d.astype(dtype)
        i = jax.lax.axis_index(ax_r)
        acc = jnp.zeros((S.s * cblock,), dtype)
        off = jnp.uint32(i) * jnp.uint32(rblock)
        for h in range(S.nnz):
            start = (h * S.n, off)
            b = S.buckets(start=start, num=rblock)  # in-shard row window
            v = S.values(dtype, start=start, num=rblock)
            acc = acc + jax.ops.segment_sum(
                d * v[lr], b[lr] * cblock + lc, num_segments=S.s * cblock
            )
        out = acc.reshape(S.s, cblock)
        return jax.lax.psum(out, ax_r)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(ax_r, ax_c, None),
            P(ax_r, ax_c, None),
            P(ax_r, ax_c, None),
        ),
        out_specs=P(None, ax_c),
    )


def rowwise_sharded_sparse(S, A, mesh: Mesh):
    """BCOO A (m, N), nonzeros owned by row block → dense A·Sᵀ (m, S),
    row-sharded.  Communication-free (≙ the ``[VC,*]`` rowwise invariant,
    P2): the hashed axis is the replicated feature axis, so each shard
    sketches its own rows with the full bucket table computed in-shard.
    """
    axes = tuple(mesh.axis_names)
    p = mesh.size
    m, n = A.shape
    if n != S.n:
        raise ValueError(f"rowwise apply needs A with {S.n} columns, got {A.shape}")
    if m % p:
        raise ValueError(f"rows {m} not divisible by mesh size {p}")
    block = m // p
    d, lr, cc = _shard_coo_rows(A, p, block)
    return _rowwise_sparse_program(S, block, mesh)(d, lr, cc)


def _rowwise_sparse_program(S, block: int, mesh: Mesh):
    """Jittable device half of :func:`rowwise_sharded_sparse` (host-side
    COO splitting done); factored out for the compiled-HLO tests."""
    axes = tuple(mesh.axis_names)

    def local(d, lr, cc):
        dtype = _coo_dtype(d)
        d, lr, cc = d[0].astype(dtype), lr[0], cc[0]
        acc = jnp.zeros((block * S.s,), dtype)
        for h in range(S.nnz):
            start = h * S.n
            b = S.buckets(start=start, num=S.n)
            v = S.values(dtype, start=start, num=S.n)
            acc = acc + jax.ops.segment_sum(
                d * v[cc], lr * S.s + b[cc], num_segments=block * S.s
            )
        return acc.reshape(block, S.s)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axes, None), P(axes, None), P(axes, None)),
        out_specs=P(axes, None),
    )

# ---------------------------------------------------------------------------
# sparse -> SPARSE sharded output (SURVEY row 65: SpParMat -> SpParMat)
# ---------------------------------------------------------------------------


class ShardedBCOO:
    """Row-block-sharded sparse sketch result with deferred duplicates.

    The TPU re-expression of the reference's distributed-sparse output
    (``sketch/hash_transform_CombBLAS.hpp:136-302``: SpParMat in,
    SpParMat out).  Each mesh shard owns the contiguous row block
    ``[k*row_block, (k+1)*row_block)`` of the logical ``shape`` and
    holds its entries as flat (data, local-row, col) arrays — padding
    entries carry ``data == 0`` at (0, 0), harmless under the
    deferred-duplicate convention (they add zero).  Nothing here is ever
    densified; ``to_bcoo``/``todense`` are explicit host-side exits.
    """

    def __init__(self, data, rows, cols, shape, row_block, mesh,
                 col_block: int | None = None):
        self.data, self.rows, self.cols = data, rows, cols
        self.shape, self.row_block, self.mesh = shape, row_block, mesh
        # 2-D grid results (√p×√p CombBLAS analogue): cols are local to
        # the shard's column block of width col_block; None = global.
        self.col_block = col_block

    @property
    def dtype(self):
        return self.data.dtype

    def to_bcoo(self) -> jsparse.BCOO:
        """Gather to one host BCOO, duplicates summed — the same
        finalize step as the local BCOO apply (``hash.py
        _apply_sparse``), for parity checks and hand-off.  Zero-data
        padding entries (the capacity slack) are dropped host-side, so
        the result's nse is entry-proportional, never buffer-sized."""
        import numpy as np

        d = np.asarray(self.data)
        r = np.asarray(self.rows)
        c = np.asarray(self.cols)
        if d.ndim == 2:  # 1-D row-block layout -> trivial 1-wide grid
            d, r, c = d[:, None], r[:, None], c[:, None]
        pr, pc = d.shape[0], d.shape[1]
        grows = r + np.arange(pr, dtype=r.dtype)[:, None, None] * self.row_block
        gcols = c + (
            np.arange(pc, dtype=c.dtype)[None, :, None] * self.col_block
            if self.col_block is not None
            else 0
        )
        keep = d.ravel() != 0
        if not keep.any():
            return jsparse.BCOO.fromdense(
                jnp.zeros(self.shape, self.data.dtype), nse=1
            )
        dk = d.ravel()[keep]
        rk, ck = grows.ravel()[keep], gcols.ravel()[keep]
        idx = jnp.stack([jnp.asarray(rk), jnp.asarray(ck)], axis=1)
        out = jsparse.BCOO((jnp.asarray(dk), idx), shape=self.shape)
        nse = min(out.nse, self.shape[0] * self.shape[1])
        return out.sum_duplicates(nse=nse)

    def todense(self):
        return self.to_bcoo().todense()

    def sketch_columnwise(self, S2, dense_output: bool = True,
                          scatter: bool = False,
                          capacity: int | None = None):
        """Apply a second sketch to this sharded sparse result WITHOUT
        leaving the device: the per-shard (data, local-row, col) arrays
        are exactly the row-block-split input of the sharded columnwise
        programs, so chaining S2·(S1·A) costs no host exit, no gather,
        and no densification in between (≙ the reference chaining
        sketches on SpParMat, e.g. sketch-and-solve pipelines over
        CombBLAS matrices).  Duplicate entries are fine — hashing is
        linear in entries.

        ``dense_output=True`` runs the dense-merge schedule (one psum;
        ``scatter`` leaves rows sharded); ``False`` runs the sparse-out
        exchange and returns another :class:`ShardedBCOO`."""
        if self.col_block is not None:
            raise ValueError(
                "chaining from a 2-D grid result is not supported — "
                "its column indices are block-local; gather via "
                "to_bcoo() first"
            )
        p = self.mesh.size
        n2, m2 = self.shape
        if S2.n != n2:
            raise ValueError(
                f"columnwise chain needs S2.n == {n2}, got {S2.n}"
            )
        if n2 >= (1 << 32):
            raise ValueError(f"sparse schedules support N < 2^32, got {n2}")
        if (scatter or not dense_output) and S2.s % p:
            # Same precondition the non-chained entry points enforce —
            # without it the failure is an opaque reduce_scatter
            # lowering error instead of this message.
            raise ValueError(
                f"chain needs S={S2.s} divisible by mesh size {p} "
                "(sharded output rows)"
            )
        if dense_output:
            return _columnwise_sparse_program(
                S2, m2, self.row_block, self.mesh, scatter
            )(self.data, self.rows, self.cols)
        cap = (
            S2.nnz * self.data.shape[1] if capacity is None else int(capacity)
        )
        dv, rv, cv = _columnwise_sparse_out_program(
            S2, self.row_block, S2.s // p, cap, self.mesh
        )(self.data, self.rows, self.cols)
        return ShardedBCOO(
            dv, rv, cv, (S2.s, m2), S2.s // p, self.mesh
        )


def columnwise_sharded_sparse_out(S, A, mesh: Mesh, capacity: int | None = None):
    """BCOO A (N, m) -> BCOO S·A (S, m), output ROW-BLOCK-SHARDED and
    never densified (closes SURVEY row 65's partial: the other P6
    schedules merge into a dense (S, m) accumulator, the wrong
    asymptotic when S is large and the output stays sparse).

    Schedule: each shard hashes its row block with shard-local counter
    windows (P5), relabels nonzeros to (bucket, col, v·val) — deferred
    duplicates, exactly the local BCOO apply — then routes every entry
    to the shard that owns its output row block through ONE tiled
    ``all_to_all`` of fixed-capacity per-destination buffers (the TPU
    answer to CombBLAS's SpParMat redistribution; ragged exchanges
    don't exist under XLA's static shapes, so capacity is the padding).

    ``capacity`` is the per-(source, destination) buffer length.  The
    default — every entry of one source landing on one destination —
    can never drop; a tighter value trades memory for silent dropping
    of overflow entries, so only pass one derived from a real count.
    Zero-value padding entries are routed to a sentinel destination and
    never occupy capacity slots, so the relevant count is the max
    per-(source, destination) number of REAL entries.
    """
    axes = tuple(mesh.axis_names)
    p = mesh.size
    n, m = A.shape
    if n != S.n:
        raise ValueError(f"columnwise apply needs A with {S.n} rows, got {A.shape}")
    if n % p:
        raise ValueError(f"rows {n} not divisible by mesh size {p}")
    if S.s % p:
        raise ValueError(
            f"sparse-out needs S={S.s} divisible by mesh size {p} "
            "(output is row-block-sharded)"
        )
    if n >= (1 << 32):
        raise ValueError(f"sparse schedules support N < 2^32, got N={n}")
    block, out_block = n // p, S.s // p
    d, lr, cc = _shard_coo_rows(A, p, block)
    entries = S.nnz * d.shape[1]
    cap = entries if capacity is None else int(capacity)
    dv, rv, cv = _columnwise_sparse_out_program(
        S, block, out_block, cap, mesh
    )(d, lr, cc)
    return ShardedBCOO(dv, rv, cv, (S.s, m), out_block, mesh)


def _exchange_entries(val, row, col, nparts: int, out_block: int, cap: int,
                      axis, my_index):
    """Route (val, row, col) entries to the mesh-axis peer owning row
    block ``row // out_block`` via ONE tiled ``all_to_all`` of
    fixed-capacity per-destination buffers (f32: values ride the packed
    int32 index exchange via bitcast; f64 takes a second exchange).
    Returns (values, LOCAL rows, cols), each (nparts, cap), for the
    receiving shard.  Shared by the 1-D and 2-D sparse-out schedules.

    Zero-value entries (COO block padding — the hash values are nonzero
    a.s., so val == 0 iff the padded data slot was 0) are routed to the
    out-of-range sentinel destination ``nparts``: they never occupy
    capacity slots, so a user capacity derived from REAL
    per-destination counts cannot drop real entries, and the
    out-of-bounds scatter row drops them before the exchange."""
    dtype = val.dtype
    dest = row // jnp.int32(out_block)
    dest = jnp.where(val == 0, jnp.int32(nparts), dest)
    # Sort by destination; position-in-segment via searchsorted.
    order = jnp.argsort(dest)
    sd = dest[order]
    starts = jnp.searchsorted(sd, jnp.arange(nparts, dtype=sd.dtype))
    pos = jnp.arange(sd.shape[0], dtype=jnp.int32) - starts[
        jnp.minimum(sd, nparts - 1)
    ].astype(jnp.int32)
    if dtype == jnp.float32:
        # Values ride the SAME packed int32 exchange (bitcast lane):
        # the buffers are the payload, but launch latency is per-op.
        buf = (
            jnp.zeros((nparts, 3, cap), jnp.int32)
            .at[sd, 0, pos].set(row[order], mode="drop")
            .at[sd, 1, pos].set(col[order], mode="drop")
            .at[sd, 2, pos].set(
                jax.lax.bitcast_convert_type(val[order], jnp.int32),
                mode="drop",
            )
        )
        rbuf = jax.lax.all_to_all(buf, axis, 0, 0, tiled=True)
        rr, rc = rbuf[:, 0], rbuf[:, 1]
        rv = jax.lax.bitcast_convert_type(rbuf[:, 2], jnp.float32)
    else:  # f64 (x64 parity runs): values need their own exchange
        buf_v = jnp.zeros((nparts, cap), dtype).at[sd, pos].set(
            val[order], mode="drop"
        )
        buf_i = (
            jnp.zeros((nparts, 2, cap), jnp.int32)
            .at[sd, 0, pos].set(row[order], mode="drop")
            .at[sd, 1, pos].set(col[order], mode="drop")
        )
        rv = jax.lax.all_to_all(buf_v, axis, 0, 0, tiled=True)
        ri = jax.lax.all_to_all(buf_i, axis, 0, 0, tiled=True)
        rr, rc = ri[:, 0], ri[:, 1]
    # Received rows are global; relabel to this shard's row block.
    # Padding entries (value 0) clip to local row 0 — harmless.
    lrows = jnp.clip(
        rr - jnp.int32(my_index) * jnp.int32(out_block), 0, out_block - 1
    )
    return rv, lrows, rc


def suggest_sparse_out_capacity(S, A, mesh: Mesh) -> int:
    """Exact per-(source, destination) REAL-entry capacity for
    :func:`columnwise_sharded_sparse_out` on this (sketch, matrix, mesh)
    — the tightest value that cannot drop (padding never counts: it
    rides the sentinel destination).  Host-side: hashes the nonzero
    global rows once with the same counter-derived buckets the schedule
    uses.  Worth calling when the default (every entry of one source on
    one destination) over-allocates badly — e.g. near-uniform hashes,
    where the true max is ≈ entries/p + O(√entries).

    1-D meshes only: the row block (n/p) and destination routing here
    assume every device sits on one axis.  On a 2-D grid rows split over
    the ROW axis only (block n/pr, exchange over pr peers), so this
    count would be wrong for :func:`columnwise_sharded_sparse_out_2d` —
    rejected rather than silently under/over-sized."""
    import numpy as np

    if len(mesh.axis_names) > 1:
        raise ValueError(
            "suggest_sparse_out_capacity is 1-D only: mesh has axes "
            f"{tuple(mesh.axis_names)}; its n/p row blocks and p-way "
            "destination counts do not match the 2-D grid's row-axis "
            "exchange (see columnwise_sharded_sparse_out_2d)"
        )
    p = mesh.size
    n = A.shape[0]
    block, out_block = n // p, S.s // p
    rows = np.asarray(A.indices[:, 0])
    data = np.asarray(A.data)
    buckets = np.asarray(S.buckets())  # (nnz*N,) flat layout
    need = 1
    for src in range(p):
        sel = (rows // block == src) & (data != 0)
        gl = rows[sel]
        if not gl.size:
            continue
        # All hash functions of one source share the destination buffer.
        dests = np.concatenate(
            [buckets[h * S.n + gl] // out_block for h in range(S.nnz)]
        )
        need = max(need, int(np.bincount(dests, minlength=p).max()))
    return need


def _columnwise_sparse_out_program(S, block: int, out_block: int, cap: int,
                                   mesh: Mesh):
    """Jittable device half of :func:`columnwise_sharded_sparse_out`;
    factored out for the compiled-HLO schedule tests (the lock: one
    all-to-all, NO psum, NO (S, m) dense accumulator)."""
    axes = tuple(mesh.axis_names)
    p = mesh.size

    def local(d, lr, cc):
        dtype = _coo_dtype(d)
        d, lr, cc = d[0].astype(dtype), lr[0], cc[0]
        idx = jax.lax.axis_index(axes)
        off = jnp.uint32(idx) * jnp.uint32(block)
        vals, rows = [], []
        for h in range(S.nnz):
            start = (h * S.n, off)
            b = S.buckets(start=start, num=block)
            v = S.values(dtype, start=start, num=block)
            vals.append(d * v[lr])
            rows.append(b[lr])
        val = jnp.concatenate(vals)              # (E,)
        row = jnp.concatenate(rows)              # global out rows [0, S)
        col = jnp.tile(cc, S.nnz)
        rv, lrows, rc = _exchange_entries(
            val, row, col, p, out_block, cap, axes, idx
        )
        flat = (1, p * cap)
        return (
            rv.reshape(flat),
            lrows.reshape(flat),
            rc.reshape(flat),
        )

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axes, None), P(axes, None), P(axes, None)),
        out_specs=(P(axes, None), P(axes, None), P(axes, None)),
    )


def columnwise_sharded_sparse_out_2d(S, A, mesh: Mesh,
                                     capacity: int | None = None):
    """BCOO A (N, m) on a 2-D grid -> BCOO S·A (S, m) on the SAME grid,
    never densified — the full SpParMat→SpParMat analogue
    (``sketch/hash_transform_CombBLAS.hpp:136-302``: the reference's
    CombBLAS matrices are natively √p×√p-distributed, and its sketch
    keeps the output on the grid).

    Nonzeros are owned by (row-block, column-block).  An entry's output
    column block is its INPUT column block (columnwise sketching leaves
    columns alone), so routing is column-local: each shard relabels its
    entries to (bucket, local col, v·val) with in-shard counter windows
    (P5) and exchanges them with its mesh-COLUMN peers through one
    tiled ``all_to_all`` over the mesh ROW axis.  Output: shard (i, j)
    owns rows [i·S/pr, (i+1)·S/pr) × cols [j·m/pc, (j+1)·m/pc).
    Communication ∝ entries, rides one mesh axis; memory is
    entry-proportional — never an (S, m/pc) dense block (contrast
    :func:`columnwise_sharded_sparse_2d`, the dense-output variant).

    ``capacity`` as in :func:`columnwise_sharded_sparse_out`: per-
    (source, destination) REAL-entry buffer length; the default cannot
    drop.  NOTE: :func:`suggest_sparse_out_capacity` is the 1-D helper
    and refuses 2-D meshes — here entries route over the ROW axis only
    (pr peers, row block n/pr), so a tight 2-D capacity must count
    per-(row-block, destination) maxima on that axis instead.
    """
    pr, pc, rblock, cblock, d, lr, lc = _validate_grid_2d(
        S, A, mesh, "columnwise_sharded_sparse_out_2d"
    )
    if S.s % pr:
        raise ValueError(
            f"sparse-out needs S={S.s} divisible by mesh rows {pr} "
            "(output rows are block-sharded over the row axis)"
        )
    out_rblock = S.s // pr
    entries = S.nnz * d.shape[2]
    cap = entries if capacity is None else int(capacity)
    dv, rv, cv = _columnwise_sparse_out_2d_program(
        S, rblock, out_rblock, cap, mesh
    )(d, lr, lc)
    return ShardedBCOO(
        dv, rv, cv, (S.s, A.shape[1]), out_rblock, mesh, col_block=cblock
    )


def _columnwise_sparse_out_2d_program(S, rblock: int, out_rblock: int,
                                      cap: int, mesh: Mesh):
    """Jittable device half of :func:`columnwise_sharded_sparse_out_2d`;
    factored out for the compiled-HLO locks (one all-to-all over the
    row axis only, NO psum, NO dense accumulator)."""
    ax_r, ax_c = mesh.axis_names
    pr = mesh.shape[ax_r]

    def local(d, lr, lc):
        dtype = _coo_dtype(d)
        d, lr, lc = d[0, 0].astype(dtype), lr[0, 0], lc[0, 0]
        i = jax.lax.axis_index(ax_r)
        off = jnp.uint32(i) * jnp.uint32(rblock)
        vals, rows = [], []
        for h in range(S.nnz):
            start = (h * S.n, off)
            b = S.buckets(start=start, num=rblock)
            v = S.values(dtype, start=start, num=rblock)
            vals.append(d * v[lr])
            rows.append(b[lr])
        val = jnp.concatenate(vals)
        row = jnp.concatenate(rows)              # global out rows [0, S)
        col = jnp.tile(lc, S.nnz)                # LOCAL cols: stay put
        rv, lrows, rc = _exchange_entries(
            val, row, col, pr, out_rblock, cap, ax_r, i
        )
        flat = (1, 1, pr * cap)
        return (
            rv.reshape(flat),
            lrows.reshape(flat),
            rc.reshape(flat),
        )

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(ax_r, ax_c, None),
            P(ax_r, ax_c, None),
            P(ax_r, ax_c, None),
        ),
        out_specs=(
            P(ax_r, ax_c, None),
            P(ax_r, ax_c, None),
            P(ax_r, ax_c, None),
        ),
    )


def rowwise_sharded_sparse_out(S, A, mesh: Mesh):
    """BCOO A (m, N), row-sharded -> BCOO A·Sᵀ (m, S), row-sharded,
    never densified.  Communication-FREE (P2: the hashed axis is the
    replicated feature axis): each shard relabels its own rows' column
    indices with the full in-shard bucket table and keeps its entries
    local — the output row owner is the input row owner."""
    axes = tuple(mesh.axis_names)
    p = mesh.size
    m, n = A.shape
    if n != S.n:
        raise ValueError(f"rowwise apply needs A with {S.n} columns, got {A.shape}")
    if m % p:
        raise ValueError(f"rows {m} not divisible by mesh size {p}")
    block = m // p
    d, lr, cc = _shard_coo_rows(A, p, block)
    dv, rv, cv = _rowwise_sparse_out_program(S, mesh)(d, lr, cc)
    return ShardedBCOO(dv, rv, cv, (m, S.s), block, mesh)


def _rowwise_sparse_out_program(S, mesh: Mesh):
    """Jittable device half of :func:`rowwise_sharded_sparse_out`;
    factored out for the compiled-HLO tests (the lock: ZERO collectives)."""
    axes = tuple(mesh.axis_names)

    def local(d, lr, cc):
        dtype = _coo_dtype(d)
        d, lr, cc = d[0].astype(dtype), lr[0], cc[0]
        vals, cols = [], []
        for h in range(S.nnz):
            start = h * S.n
            b = S.buckets(start=start, num=S.n)
            v = S.values(dtype, start=start, num=S.n)
            vals.append(d * v[cc])
            cols.append(b[cc])
        flat = (1, S.nnz * d.shape[0])
        return (
            jnp.concatenate(vals).reshape(flat),
            jnp.tile(lr, S.nnz).reshape(flat),
            jnp.concatenate(cols).reshape(flat),
        )

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axes, None), P(axes, None), P(axes, None)),
        out_specs=(P(axes, None), P(axes, None), P(axes, None)),
    )
