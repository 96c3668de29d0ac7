"""Kernel library (≙ ``ml/kernels.hpp:12-1289``).

``Kernel`` mirrors ``kernel_t``: ``gram(X, Y)`` computes the kernel matrix
and ``create_rft(s, tag, context)`` builds the matching random feature map
(tags ≙ ``ml/feature_transform_tags.hpp``: "regular", "fast", "quasi",
"sparse" where supported).

Convention: X is (n, d) with examples as **rows** (the reference's
dirX/dirY orientation tags collapse to this fixed layout; its sketches'
columnwise/rowwise tags are applied internally).  Gram matrices are
computed from sharded MXU-friendly primitives: squared-distance via the
‖x‖² + ‖y‖² − 2·X·Yᵀ expansion (≙ ``base/distance.hpp``), L1/semigroup
distances via row-blocked broadcasts (peak intermediate capped at
``_PAIRWISE_LIMIT`` elements; the reference loops the full O(n·m·d)).
"""

from __future__ import annotations

import abc
import json
import math
from typing import Any

import jax
import jax.numpy as jnp

from ..core.context import SketchContext

__all__ = [
    "Kernel",
    "LinearKernel",
    "GaussianKernel",
    "PolynomialKernel",
    "LaplacianKernel",
    "ExpSemigroupKernel",
    "MaternKernel",
    "kernel_by_name",
    "shifted_gram",
]


def _sqdist(X, Y):
    """Pairwise squared euclidean distances, (n, m) — one big matmul.

    The cross-term matmul runs at ``precision='highest'``: on TPU the
    default f32 matmul passes through bf16, and the ``xx + yy − 2·xy``
    differencing amplifies that to O(1) absolute errors on clustered data
    (nonzero self-distances → non-PSD Grams → Cholesky failures).  The
    reference computes Grams in f64 (base/distance.hpp); full-f32 MXU is
    the TPU parity point."""
    xx = jnp.sum(X * X, axis=1)[:, None]
    yy = jnp.sum(Y * Y, axis=1)[None, :]
    return jnp.maximum(xx + yy - 2.0 * jnp.dot(X, Y.T, precision="highest"), 0.0)


# Broadcast intermediates above this many elements are computed in row
# blocks (the reference's base/distance.hpp does the full O(n·m·d) loop;
# blocking keeps peak memory to one (B, m, d) slab).
_PAIRWISE_LIMIT = 1 << 27


def _blocked_rows(pair_fn, X, Y):
    n, d = X.shape
    m = Y.shape[0]
    if n * m * d <= _PAIRWISE_LIMIT:
        return pair_fn(X, Y)
    block = max(1, _PAIRWISE_LIMIT // max(m * d, 1))
    outs = [
        pair_fn(X[i : i + block], Y) for i in range(0, n, block)
    ]
    return jnp.concatenate(outs, axis=0)


def _l1dist(X, Y):
    """Pairwise L1 distances (row-blocked broadcast)."""
    return _blocked_rows(
        lambda a, b: jnp.sum(jnp.abs(a[:, None, :] - b[None, :, :]), axis=-1),
        X,
        Y,
    )


def _semigroup_dist(X, Y):
    """Pairwise semigroup "distance" sum_k sqrt(x_k + y_k) on nonnegative
    inputs (row-blocked broadcast)."""
    return _blocked_rows(
        lambda a, b: jnp.sum(
            jnp.sqrt(jnp.maximum(a[:, None, :] + b[None, :, :], 0.0)), axis=-1
        ),
        X,
        Y,
    )


def _dense(X):
    """Densify BCOO for Gram/distance paths (outputs are dense anyway)."""
    return X.todense() if hasattr(X, "todense") else jnp.asarray(X)


class Kernel(abc.ABC):
    """≙ ``kernel_t`` (``ml/kernels.hpp:12-70``)."""

    kernel_type: str = "abstract"

    def __init__(self, n: int):
        self.n = int(n)  # input dimension (≙ _N)

    @abc.abstractmethod
    def gram(self, X, Y=None):
        """K[i, j] = k(X[i], Y[j]); Y=None means Y=X (symmetric_gram)."""

    @abc.abstractmethod
    def create_rft(self, s: int, tag: str, context: SketchContext):
        """Feature map with s features approximating this kernel."""

    # -- serialization (≙ kernel_t::to_ptree) -------------------------------

    def _param_dict(self) -> dict[str, Any]:
        return {}

    def to_dict(self):
        d = {"kernel_type": self.kernel_type, "N": self.n}
        d.update(self._param_dict())
        return d

    def to_json(self):
        return json.dumps(self.to_dict())

    # -- a pytree of its parameters -------------------------------------------
    # A kernel crosses ``jax.jit`` as an argument (:func:`shifted_gram`):
    # the parameters of ``_param_dict`` are its leaves, traced, so one
    # program serves every sigma at a shape; the kind, N and the
    # parameters a subclass names in ``_static_params`` (an exponent, a
    # branch of the formula) are its structure.

    _static_params: tuple[str, ...] = ()

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        jax.tree_util.register_pytree_node_class(cls)

    def tree_flatten(self):
        params = self._param_dict()
        static = tuple((k, params.pop(k)) for k in self._static_params)
        return tuple(params.values()), (self.n, tuple(params), static)

    @classmethod
    def tree_unflatten(cls, aux, children):
        n, names, static = aux
        self = object.__new__(cls)  # not __init__: a leaf may be a tracer
        vars(self).update(zip(names, children), n=n, **dict(static))
        return self

    def __repr__(self):
        params = ", ".join(f"{k}={v}" for k, v in self._param_dict().items())
        return f"{type(self).__name__}(N={self.n}{', ' + params if params else ''})"


class LinearKernel(Kernel):
    """k(x, y) = xᵀy (≙ ``linear_t``, ml/kernels.hpp:156)."""

    kernel_type = "linear"

    def gram(self, X, Y=None):
        Y = X if Y is None else Y
        return jnp.dot(X, Y.T, precision="highest")

    def create_rft(self, s, tag, context):
        from ..sketch import CWT, FJLT, JLT

        # ≙ linear_t::create_rft: JLT regular / FJLT fast / CWT sparse.
        if tag == "regular":
            return JLT(self.n, s, context)
        if tag == "fast":
            return FJLT(self.n, s, context)
        if tag == "sparse":
            return CWT(self.n, s, context)
        raise ValueError(f"linear kernel has no {tag!r} feature transform")


class GaussianKernel(Kernel):
    """k(x, y) = exp(−‖x−y‖²/(2σ²)) (≙ ``gaussian_t``, ml/kernels.hpp:320)."""

    kernel_type = "gaussian"

    def __init__(self, n: int, sigma: float):
        super().__init__(n)
        self.sigma = float(sigma)

    def gram(self, X, Y=None):
        Y = X if Y is None else Y
        return jnp.exp(-_sqdist(X, Y) / (2.0 * self.sigma**2))

    def create_rft(self, s, tag, context):
        from ..sketch import FastGaussianRFT, GaussianQRFT, GaussianRFT

        if tag == "regular":
            return GaussianRFT(self.n, s, context, sigma=self.sigma)
        if tag == "fast":
            return FastGaussianRFT(self.n, s, context, sigma=self.sigma)
        if tag == "quasi":
            return GaussianQRFT(self.n, s, context, sigma=self.sigma)
        raise ValueError(f"gaussian kernel has no {tag!r} feature transform")

    def _param_dict(self):
        return {"sigma": self.sigma}


class PolynomialKernel(Kernel):
    """k(x, y) = (γ·xᵀy + c)^q (≙ ``polynomial_t``, ml/kernels.hpp:495)."""

    kernel_type = "polynomial"
    _static_params = ("q",)

    def __init__(self, n: int, q: int = 2, c: float = 1.0, gamma: float = 1.0):
        super().__init__(n)
        self.q = int(q)
        self.c = float(c)
        self.gamma = float(gamma)

    def gram(self, X, Y=None):
        Y = X if Y is None else Y
        return (
            self.gamma * jnp.dot(X, Y.T, precision="highest") + self.c
        ) ** self.q

    def create_rft(self, s, tag, context):
        from ..sketch import PPT

        if tag in ("regular", "fast"):
            return PPT(self.n, s, context, q=self.q, c=self.c, gamma=self.gamma)
        raise ValueError(f"polynomial kernel has no {tag!r} feature transform")

    def _param_dict(self):
        return {"q": self.q, "c": self.c, "gamma": self.gamma}


class LaplacianKernel(Kernel):
    """k(x, y) = exp(−‖x−y‖₁/σ) (≙ ``laplacian_t``, ml/kernels.hpp:671)."""

    kernel_type = "laplacian"

    def __init__(self, n: int, sigma: float):
        super().__init__(n)
        self.sigma = float(sigma)

    def gram(self, X, Y=None):
        Y = X if Y is None else Y
        return jnp.exp(-_l1dist(X, Y) / self.sigma)

    def create_rft(self, s, tag, context):
        from ..sketch import LaplacianQRFT, LaplacianRFT

        if tag == "regular":
            return LaplacianRFT(self.n, s, context, sigma=self.sigma)
        if tag == "quasi":
            return LaplacianQRFT(self.n, s, context, sigma=self.sigma)
        raise ValueError(f"laplacian kernel has no {tag!r} feature transform")

    def _param_dict(self):
        return {"sigma": self.sigma}


class ExpSemigroupKernel(Kernel):
    """k(x, y) = exp(−β·Σ_i √(x_i + y_i)) on histograms
    (≙ ``expsemigroup_t``, ml/kernels.hpp:844)."""

    kernel_type = "expsemigroup"

    def __init__(self, n: int, beta: float):
        super().__init__(n)
        self.beta = float(beta)

    def gram(self, X, Y=None):
        Y = X if Y is None else Y
        return jnp.exp(-self.beta * _semigroup_dist(X, Y))

    def create_rft(self, s, tag, context):
        from ..sketch import ExpSemigroupQRLT, ExpSemigroupRLT

        if tag == "regular":
            return ExpSemigroupRLT(self.n, s, context, beta=self.beta)
        if tag == "quasi":
            return ExpSemigroupQRLT(self.n, s, context, beta=self.beta)
        raise ValueError(f"expsemigroup kernel has no {tag!r} feature transform")

    def _param_dict(self):
        return {"beta": self.beta}


class MaternKernel(Kernel):
    """Matérn(ν, ℓ) kernel for half-integer ν (closed forms; ν = p + ½)
    (≙ ``matern_t``, ml/kernels.hpp:1010)."""

    kernel_type = "matern"
    _static_params = ("nu",)

    def __init__(self, n: int, nu: float = 0.5, l: float = 1.0):
        super().__init__(n)
        two_nu = 2.0 * nu
        if abs(two_nu - round(two_nu)) > 1e-9 or round(two_nu) % 2 != 1:
            raise ValueError(
                f"MaternKernel gram supports half-integer nu (0.5, 1.5, ...), got {nu}"
            )
        self.nu = float(nu)
        self.l = float(l)

    def gram(self, X, Y=None):
        Y = X if Y is None else Y
        r = jnp.sqrt(_sqdist(X, Y))
        p = int(round(self.nu - 0.5))
        arg = math.sqrt(2.0 * self.nu) * r / self.l
        # k(r) = exp(−arg)·(p!/(2p)!)·Σ_{i=0}^p ((p+i)!/(i!(p−i)!))(2·arg)^(p−i)
        total = jnp.zeros_like(arg)
        for i in range(p + 1):
            coef = (
                math.factorial(p + i)
                / (math.factorial(i) * math.factorial(p - i))
            )
            total = total + coef * (2.0 * arg) ** (p - i)
        scale = math.factorial(p) / math.factorial(2 * p)
        return jnp.exp(-arg) * scale * total

    def create_rft(self, s, tag, context):
        from ..sketch import FastMaternRFT, MaternRFT

        if tag == "regular":
            return MaternRFT(self.n, s, context, nu=self.nu, l=self.l)
        if tag == "fast":
            return FastMaternRFT(self.n, s, context, nu=self.nu, l=self.l)
        raise ValueError(f"matern kernel has no {tag!r} feature transform")

    def _param_dict(self):
        return {"nu": self.nu, "l": self.l}


_KERNELS = {
    "linear": LinearKernel,
    "gaussian": GaussianKernel,
    "polynomial": PolynomialKernel,
    "laplacian": LaplacianKernel,
    "expsemigroup": ExpSemigroupKernel,
    "matern": MaternKernel,
}


def kernel_by_name(name: str, n: int, **params) -> Kernel:
    """String-typed kernel factory (≙ the C API's kernel creation)."""
    if name not in _KERNELS:
        raise ValueError(f"unknown kernel {name!r}; known: {sorted(_KERNELS)}")
    return _KERNELS[name](n, **params)


def from_dict(d: dict) -> Kernel:
    d = dict(d)
    name = d.pop("kernel_type")
    n = d.pop("N")
    return kernel_by_name(name, n, **d)


# An (n, n) Gram matrix is built ``_GRAM_BLOCK`` elements at a time (a
# power-of-two count of rows, 512 MB in f32): the block and the
# temporaries of its distance and its map are all that lives beside the
# output.
_GRAM_BLOCK = 1 << 27


@jax.jit
def shifted_gram(kernel: Kernel, X, lam):
    """``kernel.gram(X) + lam·I`` as one program with one (n, n) output.

    Rows of the output are written a block at a time into the buffer the
    loop carries, ``lam`` onto the diagonal entries of each block as it
    is made: no identity matrix, no unshifted K beside the shifted one
    (op by op, ``gram(X) + lam * eye(n)`` holds five n×n arrays at its
    peak).  A block is the largest power-of-two count of rows that keeps
    it under ``_GRAM_BLOCK`` elements, and all of X when that is no fewer
    than n, which is one ``kernel.gram(X, X)``.  The last block starts at
    ``n - block``, so an ``n`` that the block does not divide re-writes a
    few rows with the values they have.  The kernel is a pytree: its
    parameters and ``lam`` are arguments of the one program a shape has.
    The operations stand under two named scopes: ``gram.block`` (a
    block's kernel values) and ``gram.write`` (the shift, the copy into
    the buffer, the buffer's zero fill).
    """
    n = X.shape[0]
    block = min(n, 1 << max(3, (_GRAM_BLOCK // n).bit_length() - 1))
    cols = jnp.arange(n)

    def rows_from(start):
        with jax.named_scope("gram.block"):
            Kb = kernel.gram(jax.lax.dynamic_slice_in_dim(X, start, block), X)
        with jax.named_scope("gram.write"):
            on_diag = cols[None, :] == (start + jnp.arange(block))[:, None]
            return jnp.where(on_diag, Kb + jnp.asarray(lam, Kb.dtype), Kb)

    if block == n:
        return rows_from(0)

    def body(i, K):
        start = jnp.minimum(i * block, n - block)
        Kb = rows_from(start)
        with jax.named_scope("gram.write"):
            return jax.lax.dynamic_update_slice_in_dim(K, Kb, start, 0)

    out = jax.eval_shape(rows_from, 0)
    with jax.named_scope("gram.write"):
        K0 = jnp.zeros((n, n), out.dtype)
    return jax.lax.fori_loop(0, -(-n // block), body, K0)
