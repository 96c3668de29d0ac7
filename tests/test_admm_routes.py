"""BlockADMM's two routes: feature blocks cached for the run, or remade
inside every iteration (``ADMMParams.cache_transforms``).

- both routes against the benchmark entry's plain reference
  (``benchmarks/entries/admm_train.py``: the recurrence written out in
  plain ``jax.numpy`` with its four products a block, importing nothing
  of the library) for hinge and squared loss: coefficients and the
  objective of every iteration;
- remade against cached at the same size: both read each block twice an
  iteration (the right-hand side's product; then the objective's and
  ``o_j``'s in one, ``ZtObar_j`` from the block's Gram matrix), and
  differ by the order of sums inside the feature GEMM;
- ``None`` picks by bytes, from a memory figure the test supplies;
- narrow rows keep an f32 state;
- a world-1 run of the distributed trainer (``ml/distributed.py``, which
  launches this module's step) is ``BlockADMMSolver.train`` bit for bit;
- labels already on the device are coded there, to the same model.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from libskylark_tpu import SketchContext
from libskylark_tpu.ml import ADMMParams, BlockADMMSolver, admm
from libskylark_tpu.ml.coding import class_indices, dummy_coding
from libskylark_tpu.ml.distributed import DistributedBlockADMMTrainer
from libskylark_tpu.ml.kernels import GaussianKernel
from libskylark_tpu.streaming import ElasticParams, RowPartition

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N, D_IN, K = 512, 6, 3
SIZES = (32, 32, 16)
MAXITER = 6
RHO, LAM = 1.0, 0.01


def entry_module():
    path = os.path.join(REPO, "benchmarks", "entries", "admm_train.py")
    spec = importlib.util.spec_from_file_location("t_admm_train_entry", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = entry_module()


def make_data(dtype=np.float32, seed=3):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((N, D_IN)).astype(np.float32)
    y = np.argmax(X @ rng.standard_normal((D_IN, K)), axis=1).astype(np.int32)
    return jnp.asarray(X).astype(dtype), y


def make_maps(sizes=SIZES, d=D_IN, sigma=2.5, seed=11):
    kern, ctx = GaussianKernel(d, sigma), SketchContext(seed=seed)
    return [kern.create_rft(s, "regular", ctx) for s in sizes]


def train(loss, X, y, maps, **kw):
    kw = {"rho": RHO, "lam": LAM, "maxiter": MAXITER, **kw}
    return BlockADMMSolver(loss, "l2", maps, ADMMParams(**kw)).train(
        X, y, classes=np.arange(K))


def reference(loss, X, y, maps, sdtype=None):
    """(Wbar, objective trace) by the entry's plain reference, f32."""
    z = {"targets": K, "ref_block": 128, "maxiter": MAXITER, "rho": RHO, "lam": LAM,
         "loss": loss}
    with jax.enable_x64(False):
        Ws = [m._underlying.realize(jnp.float32) for m in maps]
        shifts = [m.shifts(jnp.float32) for m in maps]
        answer = REF.reference_train(
            jnp.asarray(X), jnp.asarray(y), Ws, shifts, z, sdtype=sdtype)
        W, objs = REF.unpack(answer, sum(SIZES), K)
        return np.asarray(W, np.float64), np.asarray(objs, np.float64)


def apart(model, W_ref, objs_ref):
    """(coefficients' relative Frobenius distance, the objective trace's
    largest relative distance) of a trained model from the reference."""
    W = np.asarray(model.W, np.float64)
    objs = np.asarray(model.history, np.float64)
    return (np.linalg.norm(W - W_ref) / np.linalg.norm(W_ref),
            np.max(np.abs(objs - objs_ref) / objs_ref))


# f32 rows on a CPU: the program and the reference differ by f32 rounding
# in another order of sums (read 5.6e-6 on the coefficients and 1.8e-7 on
# the objective trace); the reference with its state in bfloat16 reads
# 1.9e-2 and 4.1e-4 to 9.8e-4.  1e-4 stands between them.
TOL = 1e-4


@pytest.mark.parametrize("cache", [False, True], ids=["remade", "cached"])
@pytest.mark.parametrize("loss", ["hinge", "squared"])
def test_remade_route_runs_the_reference_recurrence(loss, cache):
    X, y = make_data()
    maps = make_maps()
    model = train(loss, X, y, maps, cache_transforms=cache)
    W_ref, objs_ref = reference(loss, X, y, maps)
    dw, dobj = apart(model, W_ref, objs_ref)
    assert dw < TOL and dobj < TOL, (dw, dobj)
    assert model.info["iterations"] == MAXITER == len(model.history)
    assert model.info["objective"] == model.history[-1]


@pytest.mark.parametrize("loss", ["hinge", "squared"])
def test_a_bfloat16_state_is_told_from_the_reference(loss):
    X, y = make_data()
    maps = make_maps()
    W_ref, objs_ref = reference(loss, X, y, maps)
    W_low, objs_low = reference(loss, X, y, maps, sdtype=jnp.bfloat16)
    dw = np.linalg.norm(W_low - W_ref) / np.linalg.norm(W_ref)
    dobj = np.max(np.abs(objs_low - objs_ref) / objs_ref)
    assert dw > 100 * TOL and dobj > 3 * TOL, (dw, dobj)


@pytest.mark.parametrize("loss", ["hinge", "squared"])
@pytest.mark.parametrize("dtype,tol", [(np.float64, 1e-11), (np.float32, 2e-5)],
                         ids=["f64", "f32"])
def test_remade_and_cached_train_the_same_model(loss, dtype, tol):
    """Same step body, another order of sums inside the feature GEMM
    (rowwise against columnwise): rounding of the dtype, no more."""
    X, y = make_data(dtype)
    maps = make_maps()
    cached = train(loss, X, y, maps, cache_transforms=True)
    remade = train(loss, X, y, maps, cache_transforms=False)
    assert cached.info["transforms_cached"] == 1 and remade.info["transforms_cached"] == 0
    assert cached.info["feature_passes"] == 0 and remade.info["feature_passes"] == 1
    assert cached.info["feature_blocks"] == remade.info["feature_blocks"] == len(SIZES)
    assert remade.W.dtype == cached.W.dtype == dtype
    dw, dobj = apart(remade, np.asarray(cached.W, np.float64), np.asarray(cached.history))
    assert dw < tol and dobj < tol, (dw, dobj)


def test_remade_route_with_partitions_and_scaled_maps():
    X, y = make_data(np.float64)
    maps = make_maps()
    kw = dict(data_partitions=4, scale_maps=True)
    cached = train("hinge", X, y, maps, cache_transforms=True, **kw)
    remade = train("hinge", X, y, maps, cache_transforms=False, **kw)
    dw, dobj = apart(remade, np.asarray(cached.W), np.asarray(cached.history))
    assert dw < 1e-11 and dobj < 1e-11, (dw, dobj)


@pytest.mark.parametrize("limit,route", [(None, 1), (10**9, 1), (10**4, 0)],
                         ids=["no_limit_stated", "fits", "does_not_fit"])
def test_none_picks_the_route_by_bytes(monkeypatch, limit, route):
    """X and the blocks are (512 x 6 + 512 x 80) x 4 = 176,128 bytes:
    under half of 1e9, over half of 1e4; a backend that states no limit
    (the CPU) caches, as the trainer always did."""
    monkeypatch.setattr(admm, "_device_memory_bytes", lambda X: limit)
    X, y = make_data()
    model = train("squared", X, y, make_maps(), cache_transforms=None)
    assert model.info["transforms_cached"] == route
    held = (N * D_IN + N * sum(SIZES)) * 4
    assert held == 176_128
    assert admm._cache_fits(X, SIZES) == (limit is None or held <= admm.CACHE_FRACTION * limit)


def test_the_limit_is_the_device_s_own():
    X, _ = make_data()
    stats = next(iter(X.devices())).memory_stats() or {}
    assert admm._device_memory_bytes(X) == stats.get("bytes_limit")


@pytest.mark.parametrize("cache", [True, False], ids=["cached", "remade"])
def test_narrow_rows_keep_an_f32_state(cache):
    """bfloat16 rows: bfloat16 features, and everything else f32.  Against
    the f32 reference the model reads what bfloat16 features cost (5.5e-3
    on the coefficients, 4.1e-4 on the objective trace), not what a
    bfloat16 state costs beside them (1.9e-2 and 1.1e-3)."""
    X, y = make_data(jnp.bfloat16)
    maps = make_maps()
    solver = BlockADMMSolver("hinge", "l2", maps, ADMMParams(
        rho=RHO, lam=LAM, maxiter=MAXITER, cache_transforms=cache))
    run = solver._prepare(X, y, np.arange(K))
    assert all(leaf.dtype == jnp.float32 for leaf in jax.tree.leaves(run.state0))
    assert all(L.dtype == jnp.float32 for L in run.Ls) and run.Yp.dtype == jnp.float32
    if cache:
        assert all(Z.dtype == jnp.bfloat16 for Z in run.Zs)
    model = solver.train(X, y, classes=np.arange(K))
    assert model.W.dtype == jnp.float32
    W_ref, objs_ref = reference("hinge", X, y, maps)
    dw, dobj = apart(model, W_ref, objs_ref)
    assert dw < 1e-2 and dobj < 8e-4, (dw, dobj)


def test_narrow_routes_agree_to_f32_rounding():
    X, y = make_data(jnp.bfloat16)
    maps = make_maps()
    cached = train("hinge", X, y, maps, cache_transforms=True)
    remade = train("hinge", X, y, maps, cache_transforms=False)
    dw, dobj = apart(remade, np.asarray(cached.W, np.float64), np.asarray(cached.history))
    assert dw < 2e-5 and dobj < 2e-5, (dw, dobj)


# -- two reads of a block, remade or cached ------------------------------------

LAYOUTS = {"P1": {}, "P4_scaled": {"data_partitions": 4, "scale_maps": True}}
# Read on a CPU, coefficients / objective trace, hinge then squared:
#   f64        P1 0 / 0, 0 / 0;                          P4 scaled 0 / 0, 0 / 0
#   f32        P1 2.8e-6 / 8.6e-8, 2.5e-6 / 1.2e-7;      P4 scaled 2.8e-6 / 1.1e-7, 2.6e-6 / 6.5e-8
#   bf16 rows  P1 3.4e-6 / 7.3e-8, 3.3e-6 / 1.3e-7


@pytest.mark.parametrize("loss", ["hinge", "squared"])
@pytest.mark.parametrize("dtype,tol,layout", [
    (np.float64, 1e-11, "P1"), (np.float64, 1e-11, "P4_scaled"),
    (np.float32, 2e-5, "P1"), (np.float32, 2e-5, "P4_scaled"),
    (jnp.bfloat16, 2e-5, "P1"),  # XLA:CPU has no batched bf16 x bf16 = f32 product for P = 4
], ids=["f64-P1", "f64-P4_scaled", "f32-P1", "f32-P4_scaled", "bf16_rows-P1"])
def test_two_reads_of_a_block_train_the_four_read_model(loss, layout, dtype, tol):
    """Both routes' schedule (product (2), solve, the stacked product,
    ``G_j Wi_j``), the block made inside the iteration against the block
    kept for the run: another order of sums in the feature GEMM, the
    rounding of the state's dtype."""
    X, y = make_data(dtype)
    maps = make_maps()
    cached = train(loss, X, y, maps, cache_transforms=True, **LAYOUTS[layout])
    remade = train(loss, X, y, maps, cache_transforms=False, **LAYOUTS[layout])
    assert cached.info["block_reads"] == remade.info["block_reads"] == 2
    dw, dobj = apart(remade, np.asarray(cached.W, np.float64), np.asarray(cached.history))
    assert dw < tol and dobj < tol, (dw, dobj)


@pytest.mark.parametrize("dtype,tol", [(np.float64, 1e-12), (np.float32, 1e-5),
                                       (jnp.bfloat16, 1e-5)], ids=["f64", "f32", "bf16_rows"])
def test_gram_matrix_times_the_solve_is_the_blocks_last_product(dtype, tol):
    """``ZtObar_j = Z_j (Z_j' Wi_j)`` is ``G_j Wi_j`` with the Gram matrix
    that ``admm_factor`` keeps beside ``L_j``: no read of the block.  One
    block, Wi_j a solve with its own factor as in the iteration.  Read,
    the three blocks: f64 4.7e-15, 4.5e-15, 4.1e-15; f32 3.8e-6, 5.0e-6,
    1.2e-6; bf16 rows 2.8e-6, 3.3e-6, 7.4e-7 (on a v5e at the benchmark
    cell's size, bf16 rows: 4.0e-5, PERF.md section 6, PR 34)."""
    X, y = make_data(dtype)
    solver = BlockADMMSolver("hinge", "l2", make_maps(), ADMMParams(cache_transforms=False))
    run = solver._prepare(X, y, np.arange(K))
    assert [G.shape for G in run.Gs] == [(1, s, s) for s in SIZES]
    assert all(G.dtype == run.dtype for G in run.Gs)
    rng = np.random.default_rng(5)
    for j, s in enumerate(SIZES):
        Z = admm._block(run.spec, j, X)
        np.testing.assert_array_equal(
            np.asarray(run.Ls[j]),
            np.asarray(jnp.linalg.cholesky(run.Gs[j] + jnp.eye(s, dtype=run.dtype))))
        Wi = admm._chol_solve(run.Ls[j], jnp.asarray(rng.standard_normal((1, s, K)), run.dtype))
        read = admm._thin("psn,pkn->psk", Z, admm._thin("psk,psn->pkn", Wi, Z))
        unread = jnp.einsum("psu,puk->psk", run.Gs[j], Wi, precision="highest")
        d = float(jnp.linalg.norm(unread - read) / jnp.linalg.norm(read))
        assert unread.dtype == read.dtype == run.dtype and d < tol, (j, d)


@pytest.mark.parametrize("cache", [True, False], ids=["cached", "remade"])
def test_both_routes_keep_one_gram_matrix_a_block(cache):
    """``G_j = Z_j Z_j'`` a block and partition (a remade block is made
    inside the program, and to f32 rounding the one made here), and
    ``L_j`` is the Cholesky factor of ``G_j + I``."""
    X, y = make_data()
    solver = BlockADMMSolver("hinge", "l2", make_maps(), ADMMParams(cache_transforms=cache))
    run = solver._prepare(X, y, np.arange(K))
    assert [G.shape for G in run.Gs] == [(1, s, s) for s in SIZES]
    assert len(run.Ls) == len(SIZES)
    for j, s in enumerate(SIZES):
        Z = run.Zs[j] if cache else admm._block(run.spec, j, X)
        G = admm._gram(Z, run.dtype)
        assert run.Gs[j].dtype == run.dtype
        assert float(jnp.linalg.norm(run.Gs[j] - G) / jnp.linalg.norm(G)) < 1e-6
        np.testing.assert_array_equal(
            np.asarray(run.Ls[j]),
            np.asarray(jnp.linalg.cholesky(run.Gs[j] + jnp.eye(s, dtype=run.dtype))))


def test_three_pieces_carry_an_f32_operand_through_a_bfloat16_product():
    rng = np.random.default_rng(0)
    with jax.enable_x64(False):
        Z = jnp.asarray(rng.standard_normal((1, 16, 256)), jnp.bfloat16)
        A = jnp.asarray(rng.standard_normal((1, 3, 256)) * 37.0, jnp.float32)
        pieces = admm._pieces(A, jnp.bfloat16)
        assert pieces.shape == (3, 1, 3, 256) and pieces.dtype == jnp.bfloat16
        back = pieces.astype(jnp.float32).sum(0)
        assert float(jnp.max(jnp.abs(back - A) / jnp.abs(A))) < 2.0**-22
        exact = jnp.einsum("psn,pkn->psk", Z.astype(jnp.float32), A, precision="highest")
        got = admm._thin("psn,pkn->psk", Z, A)
        cast = jnp.einsum("psn,pkn->psk", Z, A.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
        err = lambda x: float(jnp.linalg.norm(x - exact) / jnp.linalg.norm(exact))  # noqa: E731
        assert got.dtype == jnp.float32 and err(got) < 1e-6 < 1e-3 < err(cast)
        # the block may stand on either side
        W = jnp.asarray(rng.standard_normal((1, 16, 3)), jnp.float32)
        o = admm._thin("psk,psn->pkn", W, Z)
        o_exact = jnp.einsum("psk,psn->pkn", W, Z.astype(jnp.float32), precision="highest")
        assert float(jnp.linalg.norm(o - o_exact) / jnp.linalg.norm(o_exact)) < 1e-6


# -- the distributed trainer runs this step -----------------------------------


def bits(x):
    return np.asarray(x).tobytes()


@pytest.mark.parametrize("loss", ["squared", "hinge"])
@pytest.mark.parametrize("cache", [True, None], ids=["cached", "by_bytes"])
def test_cached_route_is_the_distributed_trainers_step_bit_for_bit(loss, cache):
    """A world-1 run of ``ml/distributed.py``'s trainer (streamed blocks,
    ``admm_chunk``) is ``BlockADMMSolver.train`` (blocks made by
    ``admm_transform`` or, by bytes, the route the CPU takes; the scan
    ``admm_iterate``) to the bit, at ``test_distributed_train.py``'s
    sizes."""
    n, d, batch = 32, 4, 4
    rng = np.random.default_rng(7)
    X, y = rng.standard_normal((n, d)), np.array([1.0, 2.0] * (n // 2))
    maps = make_maps((32, 32), d, 2.0)
    params = ADMMParams(rho=1.0, lam=0.01, maxiter=8, data_partitions=2,
                        cache_transforms=cache)
    mine = BlockADMMSolver(loss, "l2", maps, params).train(X, y)
    part = RowPartition(nrows=n, batch_rows=batch, world_size=1)

    def source(start):
        return ((X[b * batch:(b + 1) * batch], y[b * batch:(b + 1) * batch])
                for b in range(start, part.num_batches))

    theirs, _ = DistributedBlockADMMTrainer(
        loss, "l2", maps, params, ElasticParams(prefetch=0)
    ).train(source, part, regression=False)
    assert mine.info["transforms_cached"] == 1
    assert bits(mine.W) == bits(theirs.W)
    assert mine.history == theirs.history


# -- labels coded where they live -----------------------------------------------


def test_device_labels_are_coded_on_the_device_to_the_same_model():
    X, y = make_data()
    labels = np.array([3, 7, 11])[y]            # any sorted label values
    maps = make_maps()
    solver = BlockADMMSolver("hinge", "l2", maps, ADMMParams(maxiter=MAXITER))
    host = solver.train(X, labels)
    device = solver.train(X, jnp.asarray(labels))
    assert bits(host.W) == bits(device.W) and host.history == device.history
    assert host.classes == device.classes == [3, 7, 11]
    sq = BlockADMMSolver("squared", "l2", maps, ADMMParams(maxiter=MAXITER))
    assert bits(sq.train(X, labels).W) == bits(sq.train(X, jnp.asarray(labels)).W)


def test_class_indices_agree_with_dummy_coding_and_refuse_a_stranger():
    y = np.array([2, 0, 1, 0, 2])
    idx, classes = class_indices(jnp.asarray(y))
    T, classes_host = dummy_coding(y)
    np.testing.assert_array_equal(classes, classes_host)
    np.testing.assert_array_equal(np.asarray(idx), np.argmax(np.asarray(T), axis=1))
    idx, classes = class_indices(jnp.asarray(y), classes=[2, 1, 0, 5])
    np.testing.assert_array_equal(classes, [0, 1, 2, 5])
    np.testing.assert_array_equal(np.asarray(idx), y)
    with pytest.raises(ValueError, match="not in classes"):
        class_indices(jnp.asarray(y), classes=[0, 1])
