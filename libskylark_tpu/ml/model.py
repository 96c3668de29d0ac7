"""Model persistence and prediction (≙ ``ml/model.hpp``).

- ``FeatureMapModel`` ≙ ``hilbert_model_t`` (model.hpp:50-276): a chain of
  serialized feature maps + a coefficient matrix; ``predict`` re-applies
  the maps.  JSON save/load reconstructs the maps through the sketch
  registry (all randomness is counter-derived, so a model is a few KB of
  JSON + the coefficients).
- ``KernelModel`` ≙ the kernel models that hold the training X
  (model.hpp:278-1255): predict via k(X_train, X_test)ᵀ·A.
- ``load_model`` ≙ ``model_container_t`` (model.hpp:1138-1255): the
  polymorphic loader that dispatches a saved model's JSON to the right
  class; the persisted ``classes`` field plays the container's
  ``get_column_coding`` role (classification models carry their label
  decoding with them).
"""

from __future__ import annotations

import json
import os
from typing import Sequence

import jax.numpy as jnp
import numpy as np
from jax.experimental import sparse as jsparse

from ..sketch.base import Dimension, from_dict as sketch_from_dict

__all__ = ["FeatureMapModel", "KernelModel", "load_model"]

_SERIAL_VERSION = 2  # tracks sketch.base.SERIAL_VERSION (stream revision)


class _Maps:
    """Feature maps as a static argument of a trainer's programs
    (``ml/admm.py``, ``ml/krr.py``): equal when their serialized forms
    are (a map is a pure function of its JSON)."""

    def __init__(self, maps):
        self.maps = tuple(maps)
        self.key = tuple(S.to_json() for S in self.maps)

    def __hash__(self):
        return hash(self.key)

    def __eq__(self, other):
        return isinstance(other, _Maps) and self.key == other.key


def _json_info(info):
    """Best-effort JSON image of a model's ``info`` dict (the recovery /
    policy ledgers attached by the training entrypoints).  Non-JSON
    leaves degrade to ``str`` rather than dropping the whole ledger."""
    if info is None:
        return None
    return json.loads(json.dumps(info, default=str))


def _dtype_from_name(name):
    try:
        return np.dtype(name)
    except TypeError:
        # Extension dtypes (bfloat16 and the rest of ml_dtypes) register
        # with numpy only through ml_dtypes (a jax dependency) — resolve
        # by attribute.
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _restore_dtype(arr, name):
    """Undo the ``.npy`` container's extension-dtype erasure: ``np.save``
    writes bfloat16 (and friends) as raw 2-byte void records, and
    ``np.load`` hands back dtype ``|V2`` — unusable in any arithmetic.
    The saved dtype name rides the model JSON; same-width void arrays
    are re-viewed (bit-exact), anything else is a plain cast."""
    if not name or str(arr.dtype) == name:
        return arr
    dt = _dtype_from_name(name)
    if arr.dtype.kind == "V" and arr.dtype.itemsize == dt.itemsize:
        return arr.view(dt)
    return arr.astype(dt)


class FeatureMapModel:
    """Coefficients W over concatenated feature-map outputs.

    ``maps`` may be empty (linear model on raw features, ≙ hilbert model
    with no transforms).  ``scale_maps`` applies the reference's
    ``sqrt(sj/d)`` block scaling (``BlockADMM.hpp:425-426``).
    """

    def __init__(self, maps: Sequence, W, scale_maps: bool = False,
                 input_dim=None, classes=None):
        self.maps = list(maps)
        self.W = jnp.asarray(W)
        self.scale_maps = bool(scale_maps)
        self.input_dim = input_dim or (self.maps[0].n if self.maps else None)
        # Label coding for classification models (≙ get_column_coding,
        # model.hpp:1242-1244); None for regression.
        self.classes = None if classes is None else list(
            np.asarray(classes).tolist()
        )
        # Training ledger (info["recovery"], info["policy"]) attached by
        # the solver entrypoints; persists through save/load.
        self.info = None

    def features(self, X):
        """Concatenated (n, D) feature matrix for X (n, d); BCOO inputs
        pass through to the maps' input-sparsity apply paths."""
        if not isinstance(X, jsparse.BCOO):
            X = jnp.asarray(X)
        if not self.maps:
            return X if not isinstance(X, jsparse.BCOO) else X.todense()
        blocks = []
        for S in self.maps:
            Z = S.apply(X, Dimension.ROWWISE)
            if self.scale_maps:
                Z = Z * jnp.asarray(
                    np.sqrt(Z.shape[-1] / X.shape[-1]), Z.dtype
                )
            blocks.append(Z)
        return jnp.concatenate(blocks, axis=-1)

    def predict(self, X):
        """(n, k) outputs (decision values / regression predictions)."""
        Z = self.features(X)
        return Z @ self.W.astype(Z.dtype)

    def predict_labels(self, X, classes=None):
        O = self.predict(X)
        idx = jnp.argmax(O, axis=-1)
        classes = classes if classes is not None else self.classes
        if classes is not None:
            return jnp.asarray(classes)[idx]
        return idx

    # -- persistence (≙ hilbert_model_t::save / load) -----------------------

    def to_dict(self):
        return {
            "skylark_object_type": "model",
            "skylark_version": _SERIAL_VERSION,
            "model_type": "feature_map",
            "scale_maps": self.scale_maps,
            "input_dim": self.input_dim,
            # normalize post-hoc numpy assignments to JSON scalars
            "classes": (None if self.classes is None
                        else np.asarray(self.classes).tolist()),
            "maps": [S.to_dict() for S in self.maps],
            "coef_shape": list(self.W.shape),
            "coef_dtype": str(self.W.dtype),
            "info": _json_info(self.info),
        }

    def save(self, path: str):
        """JSON metadata + .npy coefficients next to it (the reference
        embeds the dense coefficient text in the JSON; .npy is the
        faithful-but-binary equivalent)."""
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=1)
        np.save(self._coef_path(path), np.asarray(self.W))

    @classmethod
    def load(cls, path: str):
        with open(path) as f:
            d = json.load(f)
        if d.get("model_type") != "feature_map":
            raise ValueError(f"not a feature_map model: {d.get('model_type')}")
        W = _restore_dtype(np.load(cls._coef_path(path)), d.get("coef_dtype"))
        maps = [sketch_from_dict(md) for md in d["maps"]]
        model = cls(maps, jnp.asarray(W), scale_maps=d.get("scale_maps", False),
                    input_dim=d.get("input_dim"), classes=d.get("classes"))
        model.info = d.get("info")
        return model

    @staticmethod
    def _coef_path(path):
        return os.fspath(path) + ".coef.npy"


class KernelModel:
    """Kernel-space model: predict = k(X_test, X_train) @ A."""

    def __init__(self, kernel, X_train, A, classes=None):
        self.kernel = kernel
        self.X_train = jnp.asarray(X_train)
        self.A = jnp.asarray(A)
        self.input_dim = int(self.X_train.shape[1])
        self.info = None
        self.classes = None if classes is None else list(
            np.asarray(classes).tolist()
        )

    def predict(self, X):
        K = self.kernel.gram(jnp.asarray(X), self.X_train)  # (m, n)
        return K @ self.A

    def predict_labels(self, X, classes=None):
        O = self.predict(X)
        idx = jnp.argmax(O, axis=-1)
        classes = classes if classes is not None else self.classes
        if classes is not None:
            return jnp.asarray(classes)[idx]
        return idx

    def save(self, path: str):
        from .kernels import Kernel  # noqa: F401

        d = {
            "skylark_object_type": "model",
            "skylark_version": _SERIAL_VERSION,
            "model_type": "kernel",
            "classes": (None if self.classes is None
                        else np.asarray(self.classes).tolist()),
            "kernel": self.kernel.to_dict(),
            "data_dtypes": {
                "X_train": str(self.X_train.dtype),
                "A": str(self.A.dtype),
            },
            "info": _json_info(self.info),
        }
        with open(path, "w") as f:
            json.dump(d, f, indent=1)
        np.savez(
            os.fspath(path) + ".data.npz",
            X_train=np.asarray(self.X_train),
            A=np.asarray(self.A),
        )

    @classmethod
    def load(cls, path: str):
        from .kernels import from_dict as kernel_from_dict

        with open(path) as f:
            d = json.load(f)
        if d.get("model_type") != "kernel":
            raise ValueError(f"not a kernel model: {d.get('model_type')}")
        data = np.load(os.fspath(path) + ".data.npz")
        dtypes = d.get("data_dtypes") or {}
        model = cls(
            kernel_from_dict(d["kernel"]),
            jnp.asarray(_restore_dtype(data["X_train"], dtypes.get("X_train"))),
            jnp.asarray(_restore_dtype(data["A"], dtypes.get("A"))),
            classes=d.get("classes"),
        )
        model.info = d.get("info")
        return model


_MODEL_TYPES = {
    "feature_map": FeatureMapModel,
    "kernel": KernelModel,
}


def load_model(path: str):
    """Polymorphic model loader (≙ ``model_container_t``'s ptree dispatch,
    ``ml/model.hpp:1155-1166, 1208-1220``): reads the JSON header's
    ``model_type`` and loads through the right class.  The returned model
    carries its own label coding (``.classes``) when it was trained for
    classification."""
    with open(path) as f:
        d = json.load(f)
    mtype = d.get("model_type")
    if mtype not in _MODEL_TYPES:
        raise ValueError(
            f"unknown model_type {mtype!r} (expected one of "
            f"{sorted(_MODEL_TYPES)})"
        )
    return _MODEL_TYPES[mtype].load(path)
