"""Polynomial-kernel ridge regression through the streamed trainer: the
TensorSketch map (``sketch/ppt.py``) with its operands hoisted out of the
chunk programs' panel loops.

``PPT.apply_with_operands`` is ``PPT.apply`` bit for bit on both routes
(the complex FFT, and the bf16 half-spectrum matmul DFT that the chip
takes and ``SKYLARK_PPT_DFT=1`` forces here); the DFT route's (S, S/2)
tables are built once a program, outside the loop; the trained model is a plain
TensorSketch-plus-ridge that reads the map's draws as data; and
``model.info`` says how many panel passes the call made.  The DFT route
is forced with ``monkeypatch`` on maps of their own seeds: the chunk
programs are cached by the maps' value, and the gate is read at trace
time, so a shared value would hand one route's program to the other.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from libskylark_tpu import SketchContext
from libskylark_tpu.ml import KrrParams, PolynomialKernel, streaming_kernel_ridge
from libskylark_tpu.ml.krr import streaming_krr_chunk_programs
from libskylark_tpu.sketch import PPT

N, D, S, T, PANEL = 8192, 8, 64, 3, 4096  # a panel the DFT gate admits
SCOPES = ("ppt.hash", "ppt.dft", "ppt.product", "ppt.inverse")


def rows_of(start, rows, X):
    return jax.lax.dynamic_slice_in_dim(X, start, rows, 0)


@pytest.fixture(params=["fft", "dft"])
def route(request, monkeypatch):
    monkeypatch.delenv("SKYLARK_NO_PPT_DFT", raising=False)
    if request.param == "dft":
        monkeypatch.setenv("SKYLARK_PPT_DFT", "1")
    else:
        monkeypatch.delenv("SKYLARK_PPT_DFT", raising=False)
    return request.param


# -- the map --------------------------------------------------------------


@pytest.mark.parametrize("dim", ["rowwise", "columnwise"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_apply_with_operands_is_apply_bit_for_bit(route, dtype, dim):
    M = PPT(12, S, SketchContext(seed=5), q=2, c=1.0, gamma=0.1)
    A = jnp.asarray(np.random.default_rng(0).standard_normal((PANEL, 12)), dtype)
    A = A if dim == "rowwise" else A.T
    assert M._dft_wins(jnp.dtype(dtype), PANEL) == (route == "dft" and dtype == jnp.bfloat16)
    ops = M.hoistable_operands(dtype)
    assert (ops[2] is not None) == M._dft_wins(jnp.dtype(dtype), PANEL)
    want = np.asarray(M.apply(A, dim))
    np.testing.assert_array_equal(np.asarray(M.apply_with_operands(ops, A, dim)), want)
    np.testing.assert_array_equal(np.asarray(M.apply_with_operands(None, A, dim)), want)
    # inside a program, the operands built in its trace
    hoisted = jax.jit(lambda A: M.apply_with_operands(M.hoistable_operands(dtype), A, dim))
    np.testing.assert_array_equal(
        np.asarray(hoisted(A)), np.asarray(jax.jit(lambda A: M.apply(A, dim))(A)))
    # a batch under the gate takes the FFT route with the same operands
    thin = A[:16] if dim == "rowwise" else A[:, :16]
    np.testing.assert_array_equal(
        np.asarray(M.apply_with_operands(ops, thin, dim)), np.asarray(M.apply(thin, dim)))


def test_operands_are_memoized_by_dtype_and_route(monkeypatch):
    M = PPT(12, S, SketchContext(seed=6), q=2)
    monkeypatch.delenv("SKYLARK_PPT_DFT", raising=False)
    fft = M.hoistable_operands(jnp.bfloat16)
    assert fft is M.hoistable_operands(jnp.bfloat16) and fft[2] is None
    monkeypatch.setenv("SKYLARK_PPT_DFT", "1")
    dft = M.hoistable_operands(jnp.bfloat16)
    # the CountSketches folded into the forward tables: no sign matrices
    assert M._folds() and dft[0] == (None,) * M.q
    Tc, Ts, Rc, Rs, G = dft[2]
    assert [T.shape for T in Tc + Ts] == [(12, S // 2)] * (2 * M.q)
    assert all(T.dtype == jnp.bfloat16 for T in Tc + Ts + (G,))
    assert Rc.shape == Rs.shape == (M.q, S // 2) and Rc.dtype == Rs.dtype == jnp.float32
    assert G.shape == (2, S // 2, S)
    assert M.hoistable_operands(jnp.float64) is None


# -- the trainer against a plain TensorSketch and ridge ---------------------


def plain_features(M, X):
    """TensorSketch of the rows of X (float64), the map's draws read as
    data: each level's CountSketch of √γ·x plus √c·s_l at bucket h_l,
    multiplied in the frequency domain by numpy's FFT."""
    idx, val = (np.asarray(a) for a in M._hash_consts(jnp.float32))
    P = 1
    for l, cwt in enumerate(M._cwts):
        b = np.asarray(cwt.buckets())
        v = np.asarray(cwt.values(jnp.float32), np.float64)
        W = np.zeros((X.shape[0], M.s))
        np.add.at(W.T, b, np.sqrt(M.gamma) * v[:, None] * X.T)
        W[:, idx[l]] += np.sqrt(M.c) * val[l]
        P = P * np.fft.fft(W, axis=1)
    return np.real(np.fft.ifft(P, axis=1))


def test_the_trainer_is_a_plain_tensorsketch_ridge(route):
    # the DFT route runs bf16 features (its gate); the FFT route f32 ones
    dtype, seed, tol = {"fft": (jnp.float32, 61, 1e-5),
                        "dft": (jnp.bfloat16, 62, 1e-2)}[route]
    rng = np.random.default_rng(1)
    X = rng.standard_normal((N, D))
    Y = np.tanh(X @ rng.standard_normal((D, T)))
    Xd, lam = jnp.asarray(X, dtype), 0.5
    model = streaming_kernel_ridge(
        PolynomialKernel(D, q=2, c=1.0, gamma=1.0 / D), rows_of, (N, D),
        jnp.asarray(Y, jnp.float32), lam, S, SketchContext(seed=seed),
        KrrParams(max_split=2 * S, iter_lim=2), block_rows=PANEL,
        feature_dtype=dtype, block_args=(Xd,))
    assert model.info == {"feature_passes": 5, "feature_map": "PPT"}
    M = model.maps[0]
    assert M._dft_wins(jnp.dtype(dtype), PANEL) == (route == "dft")
    Z = plain_features(M, np.asarray(Xd, np.float64))
    C = np.linalg.solve(Z.T @ Z + lam * np.eye(S), Z.T @ Y)
    err = np.linalg.norm(Z @ (np.asarray(model.W, np.float64) - C)) / np.linalg.norm(Z @ C)
    assert err < tol, err


# -- the chunk programs' text ----------------------------------------------


@pytest.fixture(scope="module")
def dft_texts():
    """The three programs of a PPT chunk on the DFT route, lowered (the
    gate is read at trace time, so the environment is set around it)."""
    mp = pytest.MonkeyPatch()
    mp.setenv("SKYLARK_PPT_DFT", "1")
    mp.delenv("SKYLARK_NO_PPT_DFT", raising=False)
    try:
        maps = [PolynomialKernel(D, q=2, c=1.0, gamma=0.1).create_rft(
            S, "regular", SketchContext(seed=63))]
        gram, zr, apply_delta = streaming_krr_chunk_programs(
            maps, 0, N // PANEL, PANEL, rows_of, jnp.bfloat16)
        lam = jnp.float32(0.1)
        X = jnp.zeros((N, D), jnp.bfloat16)
        R = jnp.zeros((N // PANEL, PANEL, T), jnp.float32)
        W = jnp.zeros((S, T), jnp.float32)
        return {name: low.as_text(debug_info=True) for name, low in (
            ("gram", gram.lower(lam, X)), ("zr", zr.lower(lam, R, W, X)),
            ("apply_delta", apply_delta.lower(R, W, X)))}
    finally:
        mp.undo()


@pytest.mark.parametrize("program", ["gram", "zr", "apply_delta"])
def test_the_tables_are_built_once_a_program_outside_the_panel_loop(dft_texts, program):
    lines = dft_texts[program].splitlines()
    table = re.compile(rf"stablehlo\.(cosine|sine) .*tensor<{S}x{S // 2}xf32>")
    at = [i for i, ln in enumerate(lines) if table.search(ln)]
    assert len(at) == 2  # one cosine, one sine
    main = next(i for i, ln in enumerate(lines) if "func.func public @main" in ln)
    loop = next(i for i in range(main, len(lines)) if "stablehlo.while" in lines[i])
    # in @main before its loop: not in the body, nor in a function it calls
    assert all(main < i < loop for i in at)


@pytest.mark.parametrize("program", ["gram", "zr", "apply_delta"])
def test_the_programs_carry_the_four_scopes_under_the_feature_pass(dft_texts, program):
    """The transforms, the level products and the inverse ride under the
    feature pass; the hash, folded into the forward tables, is made with
    them once a program, before the panel loop."""
    text = dft_texts[program]
    for scope in SCOPES[1:]:
        assert re.search(rf"krr\.features/{re.escape(scope)}/", text), scope
    assert re.search(r'"jit\(\w+\)/ppt\.hash/', text)
    assert not re.search(r"krr\.features/ppt\.hash/", text)
