"""Preconditioner interface (≙ ``algorithms/Krylov/precond.hpp:14-135``).

The reference's ``inplace_precond_t`` / ``outplace_precond_t`` hierarchy
(id, mat, tri_inverse) becomes three small functional classes; JAX arrays
are immutable so everything is "outplace".  All applies are jit-compatible.

Each is a registered pytree — its factor the leaf, ``lower`` static — so
a preconditioner crosses ``jax.jit`` as an *argument*: the Krylov segment
(``krylov.run``) is one cached program per shape that takes R or M
from the caller, never a program with the factor baked in as a literal.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

__all__ = ["IdPrecond", "MatPrecond", "TriInversePrecond"]


@jax.tree_util.register_pytree_node_class
class IdPrecond:
    """Identity (≙ ``id_precond_t``)."""

    def apply(self, x):
        return x

    def apply_adjoint(self, x):
        return x

    def tree_flatten(self):
        return (), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls()


@jax.tree_util.register_pytree_node_class
class MatPrecond:
    """Multiply by a fixed matrix M (≙ ``mat_precond_t``): e.g. LSRN's
    V·Σ⁻¹."""

    def __init__(self, M):
        self.M = jnp.asarray(M)

    def apply(self, x):
        return self.M @ x

    def apply_adjoint(self, x):
        return self.M.T.conj() @ x

    def tree_flatten(self):
        return (self.M,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        # not through __init__: JAX unflattens with placeholder leaves too
        self = object.__new__(cls)
        (self.M,) = children
        return self


@jax.tree_util.register_pytree_node_class
class TriInversePrecond:
    """Solve against a triangular factor R (≙ ``tri_inverse_precond_t``):
    Blendenpik's R from QR(SA), applied as R⁻¹ / R⁻ᵀ."""

    def __init__(self, R, lower: bool = False):
        self.R = jnp.asarray(R)
        self.lower = bool(lower)

    def apply(self, x):
        return solve_triangular(self.R, x, lower=self.lower)

    def apply_adjoint(self, x):
        return solve_triangular(self.R.T.conj(), x, lower=not self.lower)

    def tree_flatten(self):
        return (self.R,), self.lower

    @classmethod
    def tree_unflatten(cls, lower, children):
        self = object.__new__(cls)
        (self.R,) = children
        self.lower = lower
        return self
