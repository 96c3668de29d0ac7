"""Child process for the multi-process ``jax.distributed`` tests.

Usage: ``python tests/_distributed_child.py <proc_id> <num_procs> <port>``.

Each process initializes the distributed runtime against a localhost
coordinator (≙ one rank of the reference's ``mpirun -np 2`` unit tests,
``tests/unit/CMakeLists.txt:11-38``), then runs the cross-process
checks and prints one ``CHECK <name> OK`` line per check plus a final
``DIST-OK``.  The parent treats missing lines as failures.
"""

from __future__ import annotations

import os
import sys


def main() -> int:
    proc_id, nprocs, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    # 2 virtual CPU devices per process → a 4-device global mesh spanning
    # both processes (collectives must cross the process boundary).
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2"
    ).strip()
    import jax

    # A CPU multi-process test by construction.
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nprocs,
        process_id=proc_id,
        initialization_timeout=60,
    )

    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    shard_map = jax.shard_map

    assert jax.process_count() == nprocs, jax.process_count()
    assert len(jax.devices()) == 2 * nprocs, jax.devices()
    print("CHECK world OK", flush=True)

    mesh = Mesh(np.asarray(jax.devices()), ("p",))
    nglobal = len(jax.devices())

    # -- 1. cross-process psum -------------------------------------------
    # Global arange sharded one element per device; psum must see every
    # process's contribution (gloo collectives over the loopback).
    sh = NamedSharding(mesh, P("p"))
    x = jax.make_array_from_callback(
        (nglobal,), sh, lambda idx: np.arange(nglobal, dtype=np.float32)[idx]
    )
    summed = jax.jit(
        shard_map(
            lambda a: jax.lax.psum(a, "p"), mesh=mesh,
            in_specs=P("p"), out_specs=P(),
        )
    )(x)
    got = float(np.asarray(summed.addressable_data(0))[0])
    want = float(np.arange(nglobal).sum())
    assert got == want, (got, want)
    print("CHECK psum OK", flush=True)

    # -- 2. sharded sketch parity across the process boundary ------------
    # Counter-based RNG: both processes realize the SAME JLT from
    # (seed, counter) alone, so each local shard of the P2 rowwise apply
    # must equal the matching rows of an unsharded local apply.
    from libskylark_tpu import SketchContext
    from libskylark_tpu.parallel import rowwise_sharded
    from libskylark_tpu.sketch.dense import JLT

    # Row count derived from the world size (odd worlds: 64 rows over 10
    # devices is exactly the divisibility bug -np 5 runs exist to catch).
    m, n, s = 8 * nglobal, 32, 16
    X_full = np.random.default_rng(7).standard_normal((m, n)).astype(
        np.float32
    )
    S = JLT(n, s, SketchContext(seed=21))
    ref = np.asarray(S.apply(jnp.asarray(X_full), "rowwise"))
    Xg = jax.make_array_from_callback(
        (m, n), NamedSharding(mesh, P("p", None)), lambda idx: X_full[idx]
    )
    out = rowwise_sharded(S, Xg, mesh)
    for shard in out.addressable_shards:
        np.testing.assert_allclose(
            np.asarray(shard.data), ref[shard.index], rtol=1e-5, atol=1e-6
        )
    print("CHECK sketch-parity OK", flush=True)

    # -- 2b. cross-process psum_scatter ----------------------------------
    # Row-sharded (G, G) arange; tiled psum_scatter over the lane axis
    # leaves each device its slice of the column sums — every element
    # crosses the process boundary.  Gloo may not implement every
    # collective; an UNIMPLEMENTED here degrades to a reasoned SKIP line
    # (the parent accepts either) so one missing collective cannot mask
    # the rest of the battery.
    X_np = np.arange(nglobal * nglobal, dtype=np.float32).reshape(
        nglobal, nglobal
    )
    Xsh = jax.make_array_from_callback(
        (nglobal, nglobal),
        NamedSharding(mesh, P("p", None)),
        lambda idx: X_np[idx],
    )
    # The try covers ONLY the collective execution (where UNIMPLEMENTED
    # surfaces); the value assertions run outside it, so a collective
    # that runs but miscomputes still fails the rank.
    try:
        colsums = jax.jit(
            shard_map(
                lambda a: jax.lax.psum_scatter(
                    a, "p", scatter_dimension=1, tiled=True
                ),
                mesh=mesh, in_specs=P("p", None), out_specs=P(None, "p"),
            )
        )(Xsh)
        jax.block_until_ready(colsums)
    except Exception as e:  # noqa: BLE001 — collective unsupported here
        colsums = None
        print(
            f"CHECK psum-scatter SKIP({type(e).__name__}: {str(e)[:120]})",
            flush=True,
        )
    if colsums is not None:
        want_cols = X_np.sum(axis=0)
        for shard in colsums.addressable_shards:
            np.testing.assert_allclose(
                np.asarray(shard.data), want_cols[None, shard.index[1]],
                rtol=1e-6, atol=0,
            )
        print("CHECK psum-scatter OK", flush=True)

    # -- 2c. cross-process all_to_all ------------------------------------
    # Tiled all_to_all turns the row-sharded X into the column-sharded X
    # (device i ends with X[:, i]) — a pure cross-process data exchange.
    try:
        cols = jax.jit(
            shard_map(
                lambda a: jax.lax.all_to_all(
                    a, "p", split_axis=1, concat_axis=0, tiled=True
                ),
                mesh=mesh, in_specs=P("p", None), out_specs=P(None, "p"),
            )
        )(Xsh)
        jax.block_until_ready(cols)
    except Exception as e:  # noqa: BLE001 — collective unsupported here
        cols = None
        print(
            f"CHECK all-to-all SKIP({type(e).__name__}: {str(e)[:120]})",
            flush=True,
        )
    if cols is not None:
        for shard in cols.addressable_shards:
            np.testing.assert_allclose(
                np.asarray(shard.data), X_np[:, shard.index[1]],
                rtol=0, atol=0,
            )
        print("CHECK all-to-all OK", flush=True)

    # -- 2d. P6 sparse schedule over the multi-process mesh --------------
    # columnwise_sharded_sparse's compiled program (host COO row-block
    # split + in-shard counter windows + one psum merge) with its inputs
    # built as GLOBAL arrays — the sparse schedule's psum crosses the
    # process boundary for the first time (VERDICT r4 item 3).
    from jax.experimental import sparse as jsparse

    from libskylark_tpu.parallel.collectives import (
        _columnwise_sparse_program,
        _shard_coo_rows,
    )
    from libskylark_tpu.sketch.hash import CWT

    rng = np.random.default_rng(11)
    N_sp, m_sp, s_sp = 4 * nglobal, 8, 16
    M = rng.standard_normal((N_sp, m_sp)).astype(np.float32)
    M[rng.random((N_sp, m_sp)) > 0.3] = 0.0
    A_sp = jsparse.BCOO.fromdense(jnp.asarray(M))
    S_sp = CWT(N_sp, s_sp, SketchContext(seed=29))
    block = N_sp // nglobal
    d, lr, cc = (np.asarray(a) for a in _shard_coo_rows(A_sp, nglobal, block))

    def _globalize(arr):
        return jax.make_array_from_callback(
            arr.shape, NamedSharding(mesh, P("p", None)),
            lambda idx: arr[idx],
        )

    out_sp = _columnwise_sparse_program(S_sp, m_sp, block, mesh, False)(
        _globalize(d), _globalize(lr), _globalize(cc)
    )
    ref_sp = np.asarray(S_sp.apply(A_sp, "columnwise").todense())
    np.testing.assert_allclose(
        np.asarray(out_sp.addressable_data(0)), ref_sp, rtol=1e-5, atol=1e-5
    )
    print("CHECK sparse-p6 OK", flush=True)

    # -- 2e. sparse-OUT schedule across the process boundary --------------
    # The round-5 all_to_all entry exchange (columnwise_sharded_sparse_out
    # routes relabeled nonzeros to their output-row owner): every entry
    # crosses processes here, and the result stays sharded BCOO.
    from libskylark_tpu.parallel.collectives import (
        ShardedBCOO,
        _columnwise_sparse_out_program,
    )

    if cols is None:
        # Gate on the 2c probe: the exchange needs the same gloo
        # all_to_all — degrade to the same reasoned SKIP instead of
        # crashing the rank (and poisoning the other world sizes).
        print("CHECK sparse-out SKIP(all_to_all unsupported here)",
              flush=True)
    else:
        s_so = 2 * nglobal
        S_so = CWT(N_sp, s_so, SketchContext(seed=31))
        cap_so = S_so.nnz * d.shape[1]
        dv, rv, cv = _columnwise_sparse_out_program(
            S_so, block, s_so // nglobal, cap_so, mesh
        )(_globalize(d), _globalize(lr), _globalize(cc))
        # Assemble THIS process's addressable shards and check them
        # against the local apply (full gather needs all processes; each
        # rank owns its row blocks).
        ref_so = np.asarray(S_so.apply(A_sp, "columnwise").todense())
        ob = s_so // nglobal
        for sh_d, sh_r, sh_c in zip(
            dv.addressable_shards, rv.addressable_shards,
            cv.addressable_shards,
        ):
            k = sh_d.index[0].start or 0  # global shard row = owner
            dd = np.asarray(sh_d.data).ravel()
            rr_l = np.asarray(sh_r.data).ravel()
            cc_l = np.asarray(sh_c.data).ravel()
            blk = np.zeros((ob, m_sp), np.float32)
            np.add.at(blk, (rr_l, cc_l), dd)
            np.testing.assert_allclose(
                blk, ref_so[k * ob : (k + 1) * ob], rtol=1e-5, atol=1e-5
            )
        wrapped = ShardedBCOO(dv, rv, cv, (s_so, m_sp), ob, mesh)
        assert wrapped.shape == (s_so, m_sp) and wrapped.row_block == ob
        print("CHECK sparse-out OK", flush=True)

    # -- 3. timer_report(distributed=True) over the world -----------------
    import time

    from libskylark_tpu.utils import PhaseTimer
    from libskylark_tpu.utils.timer import timer_report

    t = PhaseTimer()
    with t.phase("work"):
        time.sleep(0.2 * (proc_id + 1))  # rank-skewed totals
    report = t.report(distributed=True)
    assert f"over {nprocs} processes" in report, report
    row = next(line for line in report.splitlines() if "work" in line)
    cols = row.split()
    tmin, tmax = float(cols[1]), float(cols[2])
    assert tmax > tmin, report  # the skew must be visible in min/max
    print("CHECK timer-report OK", flush=True)

    # -- 4. mismatched phase sets must raise, not misalign ----------------
    bad = {"only_on_rank_1": 1.0} if proc_id else {"only_on_rank_0": 1.0}
    try:
        timer_report(bad, distributed=True)
    except RuntimeError as e:
        assert "different" in str(e)
        print("CHECK timer-mismatch OK", flush=True)
    else:
        raise AssertionError("mismatched phase names did not raise")

    print("DIST-OK", flush=True)
    jax.distributed.shutdown()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
