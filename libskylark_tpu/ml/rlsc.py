"""Regularized least-squares classification (≙ ``ml/rlsc.hpp:45-311``).

Each RLSC solver is its KRR counterpart on dummy-coded ±1 labels
(``ml/coding.hpp``), with argmax decoding at predict time.  Returned
models carry ``.classes`` for decoding.
"""

from __future__ import annotations

from contextlib import nullcontext

from .. import telemetry
from ..core.context import SketchContext
from .coding import dummy_coding
from .kernels import Kernel
from .krr import (
    KrrParams,
    approximate_kernel_ridge,
    faster_kernel_ridge,
    kernel_ridge,
    sketched_approximate_kernel_ridge,
)

__all__ = [
    "kernel_rlsc",
    "approximate_kernel_rlsc",
    "sketched_approximate_kernel_rlsc",
    "faster_kernel_rlsc",
]


def _classify(train_fn, labels_span: str | None = None):
    """``train_fn`` on dummy-coded labels.  The coding reads the labels
    to the host and copies the codes back; ``labels_span`` is the stage
    span it runs under, where the trainer has stage spans."""
    def wrapper(kernel: Kernel, X, y, lam: float, *args, **kwargs):
        with telemetry.span(labels_span) if labels_span else nullcontext():
            T, classes = dummy_coding(y)
        model = train_fn(kernel, X, T, lam, *args, **kwargs)
        model.classes = classes
        return model

    return wrapper


# ≙ KernelRLSC / ApproximateKernelRLSC / SketchedApproximateKernelRLSC /
# FasterKernelRLSC (rlsc.hpp:45-311).
kernel_rlsc = _classify(kernel_ridge)
approximate_kernel_rlsc = _classify(approximate_kernel_ridge)
sketched_approximate_kernel_rlsc = _classify(sketched_approximate_kernel_ridge)
faster_kernel_rlsc = _classify(faster_kernel_ridge, "faster_krr.labels")
