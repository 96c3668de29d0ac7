"""The entry kind ``svd_rand`` off the chip: the program against the
entry's plain reference at a small size, the cost function and the
collective reader against hand counts, and, in a child with four virtual
devices, the sharded call against the one-device call and the rehearsal
of ``svd_rand_1e7_k100_x4`` on a 2x2 mesh.
"""

import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import run as harness  # noqa: E402 - the harness's own look-up by name
import trace_reduce  # noqa: E402

CELL = "svd_rand_1e7_k100_x4"
ROWS, D, K, NOISE = 2048, 48, 6, 0.01


@pytest.fixture(scope="module")
def kind():
    return harness.load_module("entries", "svd_rand")


# -- the program against the plain reference --------------------------------


@pytest.fixture(scope="module")
def small(kind):
    """One seeded profile matrix on a 1x1 mesh, factored by the program
    and by the reference (f32 and, as the control, bfloat16)."""
    from libskylark_tpu import SketchContext
    from libskylark_tpu.linalg import SVDParams, approximate_svd
    from libskylark_tpu.parallel import default_mesh
    from libskylark_tpu.sketch import JLT

    mesh = default_mesh(1)
    A = kind.make_operand(11, mesh, ROWS, D, K, NOISE, 512)
    idx = jnp.arange(0, ROWS, 16, dtype=jnp.int32)[None, :]
    (U, sv, V), info = approximate_svd(
        A, K, SketchContext(seed=7), SVDParams(num_iterations=2), return_info=True)
    omega = JLT(D, 2 * K, SketchContext(seed=7)).realize(jnp.float32)
    ref, low = (kind.reference_svd(A, omega, idx, K, 2, mesh, dtype)
                for dtype in (None, jnp.bfloat16))
    got = dict(zip(kind.COMPARED, map(float, kind.compare(
        kind.pack(sv, V, U, idx, mesh), ref, D))))
    control = dict(zip(kind.COMPARED, map(float, kind.compare(low, ref, D))))
    return A, (U, sv, V), info, ref, got, control


def test_operand_is_the_profile_matrix_rank_k_plus_noise(small):
    A = np.asarray(small[0], np.float64)
    sv = np.linalg.svd(A, compute_uv=False)
    assert small[0].shape == (ROWS, D) and small[0].dtype == jnp.float32
    # K singular values of G1 G2 (about sqrt(rows * d)), then the noise's
    assert sv[K - 1] > 50 * sv[K]
    assert sv[K] < 2 * NOISE * (np.sqrt(ROWS) + np.sqrt(D))


def test_reference_is_the_truncated_svd_of_the_operand(small):
    """The reference against LAPACK in float64: the gap after sigma_k is a
    factor of hundreds, so two sweeps leave only f32 round-off (1e-5)."""
    A, _, _, ref, _, _ = small
    A64 = np.asarray(A, np.float64)
    U, sv, Vt = np.linalg.svd(A64, full_matrices=False)
    ref = np.asarray(ref, np.float64)
    assert np.max(np.abs(ref[0] - sv[:K])) / sv[0] < 1e-5
    V = ref[1:1 + D]
    assert np.linalg.norm(V @ V.T - Vt[:K].T @ Vt[:K]) < 1e-4
    rows = np.arange(0, ROWS, 16)
    assert (np.linalg.norm((ref[1 + D:] * ref[0]) @ V.T - (U[rows, :K] * sv[:K]) @ Vt[:K])
            / np.linalg.norm(U[rows, :K] * sv[:K]) < 1e-4)


@pytest.mark.parametrize("name,tol,why", [
    ("sigma_rel_err", 5e-6, "f32 round-off of two Gram orthonormalizations and a "
                            "small SVD: tens of eps; the basis's tilt enters squared"),
    ("subspace_err", 5e-6, "V's span is separated from the rest by sigma_k against "
                           "the noise: round-off is not amplified"),
    ("u_rows_err", 1e-5, "U S V' on 128 rows: round-off of the rotation Q Zt' at "
                         "highest precision; on a CPU the sweeps are f32 too"),
])
def test_program_agrees_with_the_plain_reference(small, name, tol, why):
    _, _, info, _, got, control = small
    assert info["attempts"] == 1 and info["recovery"]["attempts"][0]["verdict"] == "OK"
    assert got[name] < tol, why
    assert control[name] > 20 * tol  # a bfloat16 pipeline is far outside


def test_the_answer_is_one_array_sigma_then_v_then_sampled_u(kind, small):
    from libskylark_tpu.parallel import default_mesh

    _, (U, sv, V), _, _, _, _ = small
    idx = jnp.asarray([[5, 9, 2000]], jnp.int32)
    a = np.asarray(kind.pack(sv, V, U, idx, default_mesh(1)))
    assert a.shape == (1 + D + 3, K)
    assert (a[0] == np.asarray(sv)).all() and (a[1:1 + D] == np.asarray(V)).all()
    assert (a[1 + D:] == np.asarray(U)[[5, 9, 2000]]).all()


# -- the cost function and the collective reader, against hand counts -------


def test_svd_power_cost_is_one_chips_share_of_two_reads_of_A_a_sweep(kind):
    cost = kind.COSTS["svd_power"]
    sizes = {"rows": 40, "chips": 4, "d": 8, "s": 6, "num_iterations": 2}
    flop, nbytes = cost(sizes, {})
    m = 10                                       # a chip's rows
    assert nbytes == 2 * (2 * 4 * m * 8)         # two sweeps, two f32 reads of A
    # a sweep: A'Y and A W (2 m d s each); two passes of Y'Y and Y T (2 m s^2 each)
    assert flop == 2 * (2 * 2 * m * 8 * 6 + 4 * 2 * m * 6 * 6)
    whole, _ = cost({**sizes, "chips": 1}, {})
    assert whole == 4 * flop


US = 1000


def ev(name, start_us, dur_us):
    return (name, start_us * US, dur_us * US)


def test_op_ms_is_the_collectives_self_time_mean_over_the_planes():
    """Two chips, two steps of 100 us.  Chip 0: a while 10..60 that holds
    a fusion 10..30 and an all-reduce 30..50 (self time 20), an
    all-gather 70..75, and an all-reduce after the steps (not counted).
    Chip 1: the same all-reduce takes 30..40 (10), no all-gather."""
    read = harness.load_module("readers", "op_ms").read
    steps = [ev("bench_step_0", 0, 100), ev("bench_step_1", 100, 100)]
    ops0 = [ev("%while.1 = (f32[8]) while(...)", 10, 50),
            ev("%fusion.1 = f32[8] fusion(...)", 10, 20),
            ev("%all-reduce.3 = f32[8] all-reduce(...)", 30, 20),
            ev("%all-gather.1 = f32[8] all-gather(...)", 70, 5),
            ev("%all-reduce.3 = f32[8] all-reduce(...)", 230, 20)]
    ops1 = [ev("%while.1 = (f32[8]) while(...)", 10, 50),
            ev("%fusion.1 = f32[8] fusion(...)", 10, 20),
            ev("%all-reduce.3 = f32[8] all-reduce(...)", 30, 10)]
    mods = [ev("jit_chunk(1)", 10, 65)]
    trace = trace_reduce.Trace({"/device:TPU:0": (mods, ops0),
                                "/device:TPU:1": (mods, ops1)}, steps, [])
    run = types.SimpleNamespace(trace=trace)
    rx = "all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    # (25 + 10) / 2 chips / 2 steps, in ms
    assert read(run, {"op": rx}) == pytest.approx((25 + 10) / 2 / 2 / 1000)
    assert read(run, {"op": "all-gather"}) == pytest.approx(5 / 2 / 2 / 1000)
    assert read(run, {"op": "reduce-scatter"}) is None   # nothing to read, not 0
    assert read(types.SimpleNamespace(trace=None), {"op": rx}) is None


def test_the_collective_metric_names_the_five_kinds_of_collective():
    with open(os.path.join(REPO, "benchmarks", "layer_metrics",
                           "collective_dev_ms.json")) as fh:
        reader = json.load(fh)["reader"]
    assert reader["kind"] == "op_ms"
    assert sorted(reader["op"].split("|")) == [
        "all-gather", "all-reduce", "all-to-all", "collective-permute", "reduce-scatter"]


# -- four virtual devices, in a child ---------------------------------------


@pytest.fixture(scope="module")
def four(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("svd_mesh")
    env = {k: v for k, v in os.environ.items() if not k.startswith("SKYLARK_")}
    env.update(JAX_PLATFORMS="cpu", BENCH_RUN="7",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "cache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "benchmark", "_svd_mesh_child.py")],
        env=env, cwd=str(tmp), capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads([ln for ln in proc.stdout.splitlines() if ln.strip()][-1])
    assert got["devices"] == 4
    return got


def test_sharded_call_equals_the_one_device_call(four):
    p = four["program"]
    assert p["mesh"] == {"rows": 2, "cols": 2} and p["rows"] == 1024
    assert p["u_shards"] == [256] * 4          # U stays sharded by rows
    assert p["attempts"] == [1, 1]
    # the same arithmetic in another order of summation: f32 round-off
    assert p["sigma"] < 1e-5 and p["left"] < 1e-4 and p["right"] < 1e-4


def test_rehearsal_builds_a_2x2_mesh_and_shards_A_evenly(four):
    r = four["rehearsal"]
    assert r["mesh"] == {"rows": 2, "cols": 2} and r["chips"] == 4
    assert r["a_devices"] == 4 and r["a_shards"] == [r["rows"] // 4] * 4


def test_rehearsal_on_four_devices_is_correct(four):
    r = four["rehearsal"]
    assert r["rc"] == 0 and r["correct"] is True
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["compared"]) == {"sigma_rel_err", "subspace_err", "u_rows_err"}
    for value, limit in r["compared"].values():
        assert value <= limit
    assert r["device"]["count"] == 4
