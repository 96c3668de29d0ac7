"""Shared helpers for the CLI drivers."""

from __future__ import annotations

import numpy as np

__all__ = [
    "FILE_FORMATS",
    "add_perf_args",
    "add_policy_args",
    "add_telemetry_args",
    "load_classes",
    "load_dataset",
    "print_perf_report",
    "print_policy_report",
    "print_telemetry_report",
    "print_test_metrics",
    "scan_dims",
    "setup_perf",
    "setup_policy",
    "setup_telemetry",
    "stream_dataset",
]


def add_perf_args(p) -> None:
    """The shared compilation/plan observability flags (every driver)."""
    p.add_argument(
        "--xla-cache-dir", default=None,
        help="persistent XLA compilation cache directory (default: "
             ".jax_cache in the checkout; JAX_COMPILATION_CACHE_DIR, "
             "when set, wins over both): executables compiled in one "
             "run (plans included) are reloaded in the next",
    )
    p.add_argument(
        "--plan-stats", action="store_true",
        help="print the sketch-plan cache counters "
             "(hits/misses/traces/compile time) on exit",
    )


def setup_perf(args) -> None:
    """Place the persistent compilation cache before the first
    compilation (``utils.compile_cache.place``: the environment's
    ``JAX_COMPILATION_CACHE_DIR`` wins, then ``--xla-cache-dir``, then
    the fixed directory inside the checkout)."""
    from ..utils import compile_cache

    compile_cache.place(getattr(args, "xla_cache_dir", None))


def print_perf_report(args) -> None:
    """Emit the plan-cache counter block when --plan-stats was given."""
    if not getattr(args, "plan_stats", False):
        return
    from .. import plans

    st = plans.stats()
    print(
        "plan cache: "
        f"{st['hits']} hits / {st['misses']} misses, "
        f"{st['traces']} traces, {st['compiles']} compiles "
        f"({st['compile_seconds']:.3f}s), "
        f"{st['bypasses']} bypasses, "
        f"{st['size']}/{st['max_size']} plans resident"
        + (f", {st['evictions']} evicted" if st["evictions"] else "")
    )

def add_policy_args(p) -> None:
    """The shared adaptive-policy flags (every driver;
    docs/autotuning.md)."""
    g = p.add_mutually_exclusive_group()
    g.add_argument(
        "--policy", dest="policy", action="store_true", default=None,
        help="enable the adaptive execution policy (the default; "
             "profile-driven routing/autotuning once --policy-dir or "
             "SKYLARK_POLICY_DIR points at a profile store)",
    )
    g.add_argument(
        "--no-policy", dest="policy", action="store_false",
        help="disable the policy layer (sets SKYLARK_POLICY=0): "
             "default routes, no profile reads or writes, no warm start",
    )
    p.add_argument(
        "--policy-dir", default=None,
        help="profile-store directory (profile-<pid>.json per process); "
             "enables persistent autotuning profiles and warm-start "
             "plan/XLA-cache replay across runs",
    )


def setup_policy(args) -> None:
    """Apply the policy flags and warm-start the process.  Call AFTER
    :func:`setup_perf`, which places the compilation cache the replayed
    plans compile into."""
    import os

    from .. import policy

    if getattr(args, "policy", None) is False:
        os.environ["SKYLARK_POLICY"] = "0"
        return
    if getattr(args, "policy", None) is True:
        os.environ["SKYLARK_POLICY"] = "1"
    if getattr(args, "policy_dir", None):
        policy.configure(args.policy_dir)
    ws = policy.warm_start()
    if ws["enabled"] and (ws["plans_replayed"] or ws["xla_cache_dir"]):
        print(
            f"policy warm start: {ws['plans_replayed']} plans replayed "
            f"({ws['plans_skipped']} skipped), "
            f"xla cache {ws['xla_cache_dir'] or 'unset'}, "
            f"{ws['seconds']:.3f}s"
        )


def print_policy_report(args) -> None:
    """Close out a policy run: the decision counters, when any fired."""
    if getattr(args, "policy", None) is False:
        return
    from .. import telemetry

    counters = telemetry.snapshot()["policy"]
    if counters:
        print(f"policy: {counters}")


def add_telemetry_args(p) -> None:
    """The shared telemetry flags (every driver; docs/observability.md)."""
    p.add_argument(
        "--telemetry", action="store_true",
        help="enable the telemetry layer (sets SKYLARK_TELEMETRY=1): "
             "spans + counters in-process, and the JSONL run ledger "
             "when --telemetry-dir is also given",
    )
    p.add_argument(
        "--telemetry-dir", default=None,
        help="directory for the JSONL run ledger "
             "(ledger-<pid>.jsonl; implies --telemetry)",
    )


def _telemetry_requested(args) -> bool:
    return bool(
        getattr(args, "telemetry", False)
        or getattr(args, "telemetry_dir", None)
    )


def setup_telemetry(args) -> None:
    """Apply --telemetry/--telemetry-dir before the solve starts."""
    if not _telemetry_requested(args):
        return
    import os

    from .. import telemetry

    os.environ["SKYLARK_TELEMETRY"] = "1"
    if args.telemetry_dir:
        telemetry.configure(args.telemetry_dir)


def print_telemetry_report(args) -> None:
    """Close out a --telemetry run: one summary line + the ledger path."""
    if not _telemetry_requested(args):
        return
    from .. import telemetry

    snap = telemetry.snapshot()
    hit = snap["plan_cache_hit_rate"]
    overlap = snap["prefetch_overlap"]
    print(
        "telemetry: "
        f"plan-cache hit rate {hit if hit is not None else 'n/a'}, "
        f"prefetch overlap {overlap if overlap is not None else 'n/a'}, "
        f"guard {snap['guard'] or {}}, checkpoint {snap['checkpoint'] or {}}"
    )
    telemetry.flush()
    if telemetry.ledger_path():
        print(f"telemetry ledger -> {telemetry.ledger_path()}")


# ≙ the reference's --fileformat choices (ml/options.hpp:46-47,173-174):
# libsvm covers LIBSVM_DENSE/LIBSVM_SPARSE (the --sparse flag picks the
# container), hdf5_dense/hdf5_sparse name the layout in the file itself
# (ml/io.hpp:869-889).
FILE_FORMATS = ("libsvm", "hdf5_dense", "hdf5_sparse")


def _widen(X, y, n_features):
    """Pad X's feature axis up to ``n_features`` (a test file converted
    from a sparse split can have a smaller max feature index than the
    train file — the libsvm reader pads the same way)."""
    if n_features is None or X.shape[1] >= n_features:
        return X, y
    if hasattr(X, "todense"):  # BCOO: same triplets, wider logical shape
        from jax.experimental import sparse as jsparse

        X = jsparse.BCOO(
            (X.data, X.indices), shape=(X.shape[0], int(n_features))
        )
    else:
        X = np.pad(
            np.asarray(X), ((0, 0), (0, int(n_features) - X.shape[1]))
        )
    return X, y


def load_dataset(path, fileformat: str, sparse: bool, n_features=None):
    """(X, y) under any supported --fileformat.  For hdf5_dense,
    ``sparse`` converts to BCOO after the read (matching the libsvm
    --sparse semantics); hdf5_sparse is sparse by construction."""
    from ..io import read_hdf5, read_libsvm

    if fileformat == "libsvm":
        return read_libsvm(path, n_features=n_features, sparse=sparse)
    if fileformat == "hdf5_dense":
        return _widen(*read_hdf5(path, sparse=sparse), n_features)
    if fileformat == "hdf5_sparse":
        return _widen(*read_hdf5(path, sparse=True), n_features)
    raise ValueError(f"unknown fileformat {fileformat!r}; use {FILE_FORMATS}")


def stream_dataset(path, fileformat: str, d: int, batch: int, sparse: bool):
    """Bounded-memory (X_batch, y_batch) iterator under any
    --fileformat (the streaming-predict IO seam)."""
    from ..io import stream_hdf5, stream_libsvm

    if fileformat == "libsvm":
        return stream_libsvm(path, d, batch, sparse=sparse)
    if fileformat == "hdf5_dense":
        return stream_hdf5(path, batch, sparse=sparse)
    if fileformat == "hdf5_sparse":
        return stream_hdf5(path, batch, sparse=True)
    raise ValueError(f"unknown fileformat {fileformat!r}; use {FILE_FORMATS}")


def scan_dims(path, fileformat: str) -> tuple[int, int]:
    """Global ``(n_examples, n_features)`` of a dataset WITHOUT loading
    it — streaming drivers need the shape up front (rows address the
    sketch counter stream).  LIBSVM takes one tokenize-only pass
    (``io.scan_libsvm_dims``); HDF5 reads the stored shape."""
    if fileformat == "libsvm":
        from ..io import scan_libsvm_dims

        return scan_libsvm_dims(path)
    if fileformat in ("hdf5_dense", "hdf5_sparse"):
        from ..utils.deps import require

        h5py = require("h5py")
        with h5py.File(path, "r") as f:
            if "X" in f:
                return int(f["X"].shape[0]), int(f["X"].shape[1])
            d, n, _ = (int(v) for v in f["dimensions"][:])
            return n, d
    raise ValueError(f"unknown fileformat {fileformat!r}; use {FILE_FORMATS}")


def load_classes(modelfile):
    """Read the legacy label-decoding sidecar (pre-round-2 models; the
    coding now rides the model JSON itself — ``ml/model.py``)."""
    try:
        return np.load(str(modelfile) + ".classes.npy")
    except FileNotFoundError:
        return None


def print_test_metrics(model, Xt, yt, regression: bool) -> None:
    """Uniform test-set scoring block for all drivers."""
    if regression or getattr(model, "classes", None) is None:
        pred = np.asarray(model.predict(Xt))
        pred = pred[:, 0] if pred.ndim > 1 else pred
        err = np.linalg.norm(pred - yt) / max(np.linalg.norm(yt), 1e-30)
        print(f"Test relative error: {err:.4f}")
    else:
        pred = np.asarray(model.predict_labels(Xt, model.classes))
        acc = float((pred == yt).mean()) * 100
        print(f"Test accuracy: {acc:.2f}%")
