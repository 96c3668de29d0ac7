"""Label coding for classification (≙ ``ml/coding.hpp:7-146``).

``dummy_coding``: class labels → a ±1 one-vs-all coding matrix (the
reference's ``DummyCoding``); ``decode_labels``: argmax decode back to the
original label values.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["dummy_coding", "class_indices", "decode_labels"]


def dummy_coding(y, classes=None, dtype=None):
    """y (n,) labels → (T, classes): T (n, k) with +1 for the true class,
    −1 elsewhere.  ``classes`` is always returned sorted (explicit inputs
    are sorted and validated, since the index lookup requires it);
    ``dtype`` defaults to JAX's current default float."""
    y = np.asarray(y)
    if classes is None:
        classes = np.unique(y)
    else:
        classes = np.unique(np.asarray(classes))
        missing = np.setdiff1d(np.unique(y), classes)
        if missing.size:
            raise ValueError(f"labels {missing.tolist()} not in classes")
    if dtype is None:
        dtype = jnp.asarray(0.0).dtype
    k = len(classes)
    idx = np.searchsorted(classes, y)
    T = -np.ones((len(y), k))
    T[np.arange(len(y)), idx] = 1.0
    return jnp.asarray(T, dtype=dtype), classes


@jax.jit
def _indices_in(classes, y):
    idx = jnp.clip(jnp.searchsorted(classes, y), 0, classes.shape[0] - 1)
    return idx, jnp.all(classes[idx] == y)


def class_indices(y, classes=None):
    """Device labels y (n,) → (idx, classes): each label's index in the
    sorted ``classes``, computed where y lives in one program (what
    :func:`dummy_coding` does through NumPy, without the read of n
    labels to the host and the copy back; one flag comes back, to refuse
    a label that is not in ``classes``).  ``classes`` is returned sorted,
    as a NumPy array; left out, it is the labels' own set."""
    y = jnp.asarray(y)
    classes = np.asarray(jnp.unique(y)) if classes is None else np.unique(
        np.asarray(classes))
    idx, known = _indices_in(jnp.asarray(classes, y.dtype), y)
    if not bool(known):
        missing = np.setdiff1d(np.unique(np.asarray(y)), classes)
        raise ValueError(f"labels {missing.tolist()} not in classes")
    return idx, classes


def decode_labels(O, classes):
    """(n, k) outputs → (n,) labels by argmax (≙ coding.hpp decode)."""
    idx = jnp.argmax(jnp.asarray(O), axis=-1)
    return jnp.asarray(np.asarray(classes))[idx]
