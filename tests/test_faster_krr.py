"""``skylark_krr -a 1`` at a small size on the CPU: ``faster_kernel_ridge``
and ``faster_kernel_rlsc`` against the benchmark entry's plain reference,
the row-blocked shifted Gram matrix against ``kernel.gram(X) + lam I``,
the feature-map preconditioner as a pytree that rides the cached Krylov
segment, what it buys in iterations, and the checkpointed route.

The comparisons with the reference run with x64 off, as the benchmark
and the chip run the program (the suite's conftest turns it on).
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _builds import builds

from libskylark_tpu import SketchContext, ml
from libskylark_tpu.core import precision
from libskylark_tpu.ml import kernels
from libskylark_tpu.ml.krr import _FeatureMapPrecond
from libskylark_tpu.solvers import KrylovParams, cg, krylov

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _entry_module():
    path = os.path.join(REPO, "benchmarks", "entries", "krr_faster.py")
    spec = importlib.util.spec_from_file_location("t_entries_krr_faster", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ENTRY = _entry_module()
with open(os.path.join(REPO, "benchmarks", "configs", "krr_faster_mnist_f32.json")) as fh:
    CONFIG = json.load(fh)
REHEARSAL = {**CONFIG, **CONFIG["rehearsal"]}
SMALL = {**REHEARSAL, "rows": 512, "block": 128, "ref_block": 128, "sample_rows": 128}


def problem(z, seed=5):
    X, labels = ENTRY.make_data(seed, z["data_seed"], z)
    return ml.GaussianKernel(z["d"], z["sigma"]), X, labels


def fit(z, kernel, X, labels, tolerance, rlsc=True):
    train = ml.faster_kernel_rlsc if rlsc else ml.faster_kernel_ridge
    return train(kernel, X, labels, z["lam"], z["s"],
                 SketchContext(seed=z["sketch_seed"]), ml.KrrParams(tolerance=tolerance))


# -- against the plain reference ----------------------------------------------


@pytest.fixture(scope="module")
def referenced():
    """The small problem, its K_ref, codes and reference coefficients."""
    with jax.enable_x64(False):
        entry = ENTRY.Entry(CONFIG, {"limits": {}}, 5, 1, tiny=True)
        entry.sizes = dict(SMALL)
        entry.setup()
        return entry, entry.reference()


def errors(K, Y, alpha, alpha_ref, lam):
    idx = jnp.arange(128)
    resid, pred = ENTRY.compare(K, Y, jnp.float32(lam), idx,
                                ENTRY.sampled_predictions(K, alpha_ref, idx), alpha)
    coef = jnp.linalg.norm(alpha - alpha_ref) / jnp.linalg.norm(alpha_ref)
    return float(resid), float(pred), float(coef)


@pytest.mark.parametrize("rlsc", [True, False], ids=["rlsc", "ridge"])
def test_the_fit_agrees_with_the_plain_reference(referenced, rlsc):
    """CG to 1e-5 leaves a residual and predictions within 5e-5 of the
    reference's (read: 8e-6 on both) and coefficients within 2e-3 (read:
    2.9e-4, the floor of f32 at this system's condition: CG to 1e-6
    reads the same).  A Gram matrix rounded to bfloat16 reads 0.81, 0.28
    and 128, so a product at the TPU's default precision cannot pass."""
    entry, (K, Y, alpha_ref) = referenced
    z = entry.sizes
    with jax.enable_x64(False):
        kernel = ml.GaussianKernel(z["d"], z["sigma"])
        model = fit(z, kernel, entry.X, entry.Y if rlsc else Y, 1e-5, rlsc)
        resid, pred, coef = errors(K, Y, model.A, alpha_ref, z["lam"])
        assert model.A.dtype == jnp.float32 and int(model.info["flag"]) == 0
        assert model.info["precond_features"] == z["s"]
        assert resid < 5e-5 and pred < 5e-5 and coef < 2e-3
        if rlsc:
            assert list(model.classes) == list(range(z["targets"]))
            hit = jnp.mean(model.predict_labels(entry.X) == entry.Y)
            assert float(hit) > 0.99  # the classes are ten separated means
        rounded = K.astype(jnp.bfloat16).astype(jnp.float32)
        alpha_bf16 = jnp.linalg.solve(
            rounded + z["lam"] * jnp.eye(z["rows"], dtype=jnp.float32), Y)
        resid, pred, coef = errors(K, Y, alpha_bf16, alpha_ref, z["lam"])
        assert resid > 1e-2 and pred > 1e-2 and coef > 1e-1


def test_the_default_tolerance_is_the_stated_accuracy(referenced):
    """``KrrParams()``: every column's residual under 1e-3 of its code,
    against a K the program did not make."""
    entry, (K, Y, alpha_ref) = referenced
    z = entry.sizes
    with jax.enable_x64(False):
        model = ml.faster_kernel_rlsc(
            ml.GaussianKernel(z["d"], z["sigma"]), entry.X, entry.Y, z["lam"], z["s"],
            SketchContext(seed=z["sketch_seed"]))
        resid = Y - K @ model.A - z["lam"] * model.A
        rel = jnp.linalg.norm(resid, axis=0) / jnp.linalg.norm(Y, axis=0)
        assert float(rel.max()) < 1.05e-3 and float(rel.max()) > 1e-5


# -- the shifted Gram matrix ----------------------------------------------------


KERNELS = {
    "gaussian": ml.GaussianKernel(6, 2.5),
    "laplacian": ml.LaplacianKernel(6, 3.0),
    "polynomial": ml.PolynomialKernel(6, q=2, c=1.0, gamma=0.5),
    "matern": ml.MaternKernel(6, nu=1.5, l=2.0),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64], ids=["f32", "f64"])
@pytest.mark.parametrize("block", [None, 16, 32, 64], ids=lambda b: f"block{b}")
@pytest.mark.parametrize("name", KERNELS)
def test_the_blocked_shifted_gram_is_gram_plus_lambda_on_the_diagonal(
        name, block, dtype, monkeypatch):
    """Whole (the library's ``_GRAM_BLOCK`` at this size), in blocks that
    divide n = 96, and in blocks of 64 rows, which do not (the last one
    re-writes rows of the one before): the entries of ``kernel.gram(X) +
    lam * eye(n)``, the Gaussian's (the benchmark's) to the last ulps and
    its diagonal bit for bit; the others' to the rounding of their sums,
    which one fused program orders otherwise than op-by-op dispatch."""
    kernel, n, lam = KERNELS[name], 96, 0.37
    if block is not None:
        monkeypatch.setattr(kernels, "_GRAM_BLOCK", block * n)
    kernels.shifted_gram.clear_cache()
    X = jnp.asarray(np.random.default_rng(3).standard_normal((n, 6)), dtype)
    want = kernel.gram(X) + lam * jnp.eye(n, dtype=dtype)
    got = kernels.shifted_gram(kernel, X, lam)
    kernels.shifted_gram.clear_cache()
    assert got.dtype == want.dtype and got.shape == (n, n)
    ulps = (4 if name == "gaussian" else 64) * float(jnp.finfo(dtype).eps)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=ulps, atol=0)
    if name == "gaussian":
        np.testing.assert_array_equal(np.diag(np.asarray(got)), np.diag(np.asarray(want)))


def test_one_gram_program_serves_every_sigma():
    """A kernel is a pytree: its parameters are leaves, traced like
    ``lam``, so another sigma at the same shape builds nothing; what
    shapes the program (the kind, an exponent) is structure.  A kernel
    hashes and compares as any object does."""
    kernels.shifted_gram.clear_cache()
    X = jnp.asarray(np.random.default_rng(1).standard_normal((8, 3)), jnp.float32)
    kernels.shifted_gram(ml.GaussianKernel(3, 1.5), X, 0.1)
    with builds() as seen:
        got = kernels.shifted_gram(ml.GaussianKernel(3, 2.5), X, 0.2)
    assert seen == [] and kernels.shifted_gram._cache_size() == 1
    want = ml.GaussianKernel(3, 2.5).gram(X) + 0.2 * jnp.eye(8, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    kernels.shifted_gram(ml.LaplacianKernel(3, 1.5), X, 0.1)
    kernels.shifted_gram(ml.PolynomialKernel(3, q=2, gamma=0.5), X, 0.1)
    kernels.shifted_gram(ml.PolynomialKernel(3, q=2, gamma=0.7), X, 0.1)
    assert kernels.shifted_gram._cache_size() == 3
    kernels.shifted_gram(ml.PolynomialKernel(3, q=3, gamma=0.5), X, 0.1)
    assert kernels.shifted_gram._cache_size() == 4
    leaves, tree = jax.tree.flatten(ml.MaternKernel(3, nu=1.5, l=2.0))
    back = jax.tree.unflatten(tree, leaves)
    assert leaves == [2.0] and type(back) is ml.MaternKernel
    assert back.to_dict() == ml.MaternKernel(3, nu=1.5, l=2.0).to_dict()
    assert ml.GaussianKernel(3, 1.5) != ml.GaussianKernel(3, 1.5)


def test_the_default_block_is_a_power_of_two_under_the_element_limit():
    """At the benchmark's 49,152 rows a block is 2,048 rows (403 MB in
    f32); nothing n x n is made to find that out."""
    seen = []

    class Spy(ml.GaussianKernel):
        def gram(self, X, Y=None):
            seen.append(X.shape[0])
            return super().gram(X, Y)

    X = jax.ShapeDtypeStruct((49_152, 8), jnp.float32)
    out = jax.eval_shape(lambda X: kernels.shifted_gram(Spy(8, 1.0), X, 0.01), X)
    assert out.shape == (49_152, 49_152) and set(seen) == {2048}


# -- the preconditioner ---------------------------------------------------------


def test_the_preconditioner_is_a_pytree_of_its_factor_and_lambda():
    kernel, X, _ = problem(SMALL)
    P = _FeatureMapPrecond.build(kernel, 0.01, X, 64, SketchContext(seed=3), ml.KrrParams())
    leaves, tree = jax.tree.flatten(P)
    assert [leaf.shape for leaf in leaves] == [(64, X.shape[0]), ()]
    back = jax.tree.unflatten(tree, leaves)
    R = jnp.asarray(np.random.default_rng(0).standard_normal((X.shape[0], 3)), X.dtype)
    np.testing.assert_array_equal(np.asarray(back.apply(R)), np.asarray(P.apply(R)))
    np.testing.assert_array_equal(
        np.asarray(jax.jit(lambda P, R: P.apply(R))(P, R)).shape, R.shape)
    # Woodbury: apply is (Z Z' + lam I)^-1, with Z the map's own features
    S = kernel.create_rft(64, "regular", SketchContext(seed=3))
    Z = np.asarray(S.apply(X, "rowwise"), np.float64)
    want = np.linalg.solve(Z @ Z.T + 0.01 * np.eye(X.shape[0]), np.asarray(R, np.float64))
    np.testing.assert_allclose(np.asarray(P.apply(R)), want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("n", [64, 256, 300], ids=lambda n: f"n{n}")
def test_a_long_product_is_the_sum_of_its_blocks(n, monkeypatch):
    """``long_dot`` multiplies ``LONG_DOT_BLOCK`` terms at a time: one
    block, a whole number of blocks, and a remainder, against the product
    in float64."""
    monkeypatch.setattr(precision, "LONG_DOT_BLOCK", 128)
    precision.long_dot.clear_cache()
    rng = np.random.default_rng(n)
    A, B = rng.standard_normal((5, n)), rng.standard_normal((n, 3))
    got = precision.long_dot(jnp.asarray(A, jnp.float32), jnp.asarray(B, jnp.float32))
    assert got.dtype == jnp.float32 and got.shape == (5, 3)
    np.testing.assert_allclose(np.asarray(got), A @ B, rtol=0, atol=2e-5)
    assert precision.long_dot(jnp.asarray(A), jnp.asarray(B)).dtype == jnp.float64
    precision.long_dot.clear_cache()


def test_a_second_fit_at_the_same_shape_lowers_nothing():
    """Gram, feature map, Woodbury factor and CG are cached programs: a
    warm fit traces and lowers nothing, rides ``krylov.run`` (no lifted
    segment) and answers with the same bytes."""
    kernel, X, labels = problem(SMALL)
    krylov.run.clear_cache()
    cold = fit(SMALL, kernel, X, labels, 1e-3)
    assert krylov.run._cache_size() == 1
    with builds() as seen:
        warm = fit(SMALL, ml.GaussianKernel(SMALL["d"], SMALL["sigma"]), X, labels, 1e-3)
    assert seen == []
    assert krylov.run._cache_size() == 1
    assert np.asarray(warm.A).tobytes() == np.asarray(cold.A).tobytes()
    assert int(warm.info["iterations"]) == int(cold.info["iterations"])


def test_preconditioned_cg_takes_a_third_of_the_iterations_or_fewer():
    """The rehearsal problem (1024 rows, 48 columns, 128 features): the
    feature-map preconditioner is what makes ``-a 1`` faster."""
    with jax.enable_x64(False):
        kernel, X, labels = problem(REHEARSAL)
        model = fit(REHEARSAL, kernel, X, labels, REHEARSAL["tolerance"])
        Y = jnp.where(labels[:, None] == jnp.arange(REHEARSAL["targets"]), 1.0, -1.0)
        _, plain = cg(kernels.shifted_gram(kernel, X, REHEARSAL["lam"]),
                      Y.astype(jnp.float32),
                      params=KrylovParams(tolerance=REHEARSAL["tolerance"], iter_lim=1000))
        with_precond, without = int(model.info["iterations"]), int(plain["iterations"])
        assert int(plain["flag"]) == 0 and int(model.info["flag"]) == 0
        assert 10 <= with_precond <= 40
        assert without >= 3 * with_precond


# -- the checkpointed route -------------------------------------------------------


@pytest.mark.faults
def test_the_checkpointed_route_resumes_bit_for_bit(tmp_path):
    """``KrrParams(checkpoint_dir=...)``: a run that stopped after two
    chunks (its iteration limit) and is resumed answers with the bytes of
    the run that was never stopped; Gram matrix and preconditioner are
    rebuilt from (X, context), only CG's carry rides the checkpoint."""
    kernel, X, labels = problem(SMALL)
    Y = jnp.where(labels[:, None] == jnp.arange(SMALL["targets"]), 1.0, -1.0)

    def run(ckdir, iter_lim, resume=False):
        return ml.faster_kernel_ridge(
            kernel, X, Y, SMALL["lam"], SMALL["s"], SketchContext(seed=SMALL["sketch_seed"]),
            ml.KrrParams(tolerance=1e-8, iter_lim=iter_lim, checkpoint_dir=str(ckdir),
                         checkpoint_every=3, resume=resume))

    whole = run(tmp_path / "whole", 200)
    stopped = run(tmp_path / "ck", 6)
    assert int(stopped.info["iterations"]) == 6 and int(stopped.info["flag"]) == 1
    resumed = run(tmp_path / "ck", 200, resume=True)
    assert int(resumed.info["iterations"]) == int(whole.info["iterations"]) > 6
    assert int(resumed.info["flag"]) == 0
    assert np.asarray(resumed.A).tobytes() == np.asarray(whole.A).tobytes()
    unchunked = ml.faster_kernel_ridge(
        kernel, X, Y, SMALL["lam"], SMALL["s"], SketchContext(seed=SMALL["sketch_seed"]),
        ml.KrrParams(tolerance=1e-8, iter_lim=200))
    assert np.asarray(unchunked.A).tobytes() == np.asarray(whole.A).tobytes()
