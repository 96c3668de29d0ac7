"""Sketch transform protocol, type registry, and JSON serialization.

TPU-native re-design of the reference's sketch layer scaffolding:

- ``SketchTransform`` ≙ ``sketch_transform_t<In, Out>``
  (``sketch/sketch_transform.hpp:16-48``), with the C++ tag dispatch
  (``columnwise_tag``/``rowwise_tag``) replaced by a ``Dimension`` enum and
  the per-(input-type × output-type) template specializations replaced by a
  single JAX implementation that works for any sharding under GSPMD.
- The JSON registry ≙ ``sketch/sketch_add.hpp:15-52`` — every concrete
  transform registers its ``sketch_type`` string so serialized sketches can
  be reconstructed by name (used by the C API / Python layer in the
  reference, and by model persistence here).
- Serialization keeps the reference's property-tree schema in spirit
  (``sketch/sketch_transform_data.hpp:64-71``): a sketch is reconstructible
  from ``(sketch_type, N, S, creation_context, params)`` — ~100 bytes of
  JSON — because all randomness is counter-derived.

Conventions (fixing the reference's math in array terms):

- A transform maps R^N -> R^S.  Its logical sketch matrix ``Omega`` has
  shape ``(S, N)``.
- ``apply(A, Dimension.COLUMNWISE)``: ``A`` is ``(N, m)``; result is
  ``Omega @ A`` with shape ``(S, m)`` — each *column* of A is sketched.
- ``apply(A, Dimension.ROWWISE)``: ``A`` is ``(m, N)``; result is
  ``A @ Omega.T`` with shape ``(m, S)`` — each *row* of A is sketched.

This matches ``sketch/transforms.hpp:12-18`` (S·A columnwise, A·Sᵀ rowwise).
"""

from __future__ import annotations

import abc
import enum
import json
from typing import Any, Callable, ClassVar

import jax
import jax.numpy as jnp

from ..core.context import SketchContext

__all__ = [
    "Dimension",
    "SketchTransform",
    "register_sketch",
    "sketch_registry",
    "create_sketch",
    "from_dict",
    "from_json",
    "SERIAL_VERSION",
]

# Version 2: the f32 uniform stream switched to hi-leading bits (see
# docs/counter_contract.md "Stream revisions") — version-1 artifacts whose
# f32-uniform-derived values matter (UST/NURST selections, RFT shifts,
# Fastfood permutations realized in f32) reproduce differently.
SERIAL_VERSION = 2


class Dimension(enum.Enum):
    """Which dimension of A is sketched (≙ columnwise_tag / rowwise_tag)."""

    COLUMNWISE = "columnwise"
    ROWWISE = "rowwise"

    @classmethod
    def of(cls, d: "Dimension | str") -> "Dimension":
        if isinstance(d, Dimension):
            return d
        return cls(str(d).lower())


COLUMNWISE = Dimension.COLUMNWISE
ROWWISE = Dimension.ROWWISE

_REGISTRY: dict[str, type["SketchTransform"]] = {}


def register_sketch(cls: type["SketchTransform"]) -> type["SketchTransform"]:
    """Class decorator: register under ``cls.sketch_type`` (≙ sketch_add.hpp)."""
    _REGISTRY[cls.sketch_type] = cls
    return cls


def sketch_registry() -> dict[str, type["SketchTransform"]]:
    return dict(_REGISTRY)


class SketchTransform(abc.ABC):
    """A random linear (or feature) map R^N -> R^S, reconstructible from JSON.

    Subclass contract:
    - ``__init__(n, s, ..., context)`` must snapshot ``context`` (seed +
      counter) *before* reserving, into ``self._creation_context``, then
      reserve all counter blocks it needs.  The helper ``_snapshot`` does
      the first part.
    - ``_param_dict()`` returns the extra JSON fields (e.g. ``sigma``).
    - ``_from_param_dict(d, ctx)`` (classmethod) rebuilds from those fields.
    """

    sketch_type: ClassVar[str] = "Abstract"

    # Batch sizes at which the apply switches algorithms (bucketed plans
    # must not pad across one — the planned batch has to take the same
    # code path, and produce the same bits, as the eager ragged apply).
    batch_size_gates: ClassVar[tuple] = ()

    # True when apply_slice_kernel is implemented (jit-safe traced-start
    # COLUMNWISE partials — the enabler for bucketed streaming plans).
    supports_slice_kernel: ClassVar[bool] = False

    def __init__(self, n: int, s: int, context: SketchContext):
        if n <= 0 or s <= 0:
            raise ValueError(f"sketch dims must be positive, got N={n}, S={s}")
        self.n = int(n)
        self.s = int(s)
        self._creation_context = SketchContext(
            seed=context.seed, counter=context.counter
        )

    # -- core op ------------------------------------------------------------

    @abc.abstractmethod
    def apply(self, A, dim: Dimension | str = Dimension.COLUMNWISE):
        """Sketch ``A`` along ``dim``; returns a new array (functional)."""

    def __call__(self, A, dim: Dimension | str = Dimension.COLUMNWISE):
        return self.apply(A, dim)

    def apply_planned(self, A, dim: Dimension | str = Dimension.COLUMNWISE):
        """Plan-aware apply: route through the process-wide plan cache
        (one fused jit executable per ``(sketch JSON, dim, shape, dtype,
        sharding)`` — bitwise identical to :meth:`apply`; see
        ``libskylark_tpu.plans``).  ``SKYLARK_NO_PLANS=1`` makes this a
        plain eager :meth:`apply`."""
        from .. import plans

        return plans.apply(self, A, dim)

    # -- partial-sketch protocol (streaming / out-of-core) -------------------
    #
    # Every transform here is a linear map (or linear-then-pointwise feature
    # map) whose randomness is counter-addressable, so ``S·A`` decomposes
    # exactly into per-block contributions that never need the full A (or
    # the full Omega) resident:
    #
    # - COLUMNWISE (A is (N, m), sketched axis = rows): the block of rows
    #   [start, start+k) contributes ``Omega[:, start:start+k] @ A_block``;
    #   block contributions MERGE BY SUM, then :meth:`finalize_slices`
    #   (identity for linear sketches; the cos epilogue for RFT).
    # - ROWWISE (A is (m, N), sketched axis = columns): a block of rows
    #   (examples) carries the full feature axis, so its contribution is
    #   the finished sketch of the block; contributions MERGE BY CONCAT
    #   along axis 0 in stream order.
    #
    # ``streaming.sketch`` drives this over ``io`` batch sources with a
    # prefetch pipeline and resilient checkpoints (docs/streaming.md).

    def apply_slice(self, A_block, start: int, dim: Dimension | str = Dimension.COLUMNWISE):
        """Exact contribution of the block of A starting at row ``start``
        of the sketched axis (``start`` must be a host int — it addresses
        the counter stream, not a traced value).

        COLUMNWISE: ``A_block`` is rows [start, start+k) of the (N, m)
        input; returns the (S, m) partial ``Omega[:, start:start+k] @
        A_block``.  Sum the results over a disjoint cover of [0, N) and
        pass the total through :meth:`finalize_slices` to get ``apply(A)``
        (bit-equal modulo floating-point summation order).

        ROWWISE: ``A_block`` is any row block of the (m, N) input; returns
        the finished (k, S) sketch of those rows (``start`` only records
        stream position).  Concatenate in stream order.
        """
        dim = Dimension.of(dim)
        if dim is Dimension.ROWWISE:
            return self.apply(A_block, dim)
        start = int(start)
        k = A_block.shape[0]
        if start < 0 or start + k > self.n:
            raise ValueError(
                f"slice [{start}, {start + k}) outside the sketch domain "
                f"[0, {self.n})"
            )
        squeeze = getattr(A_block, "ndim", 2) == 1
        if squeeze:
            A_block = A_block[:, None]
        out = self._apply_slice_columnwise(A_block, start)
        return out[:, 0] if squeeze else out

    def _apply_slice_columnwise(self, A_block, start: int):
        """Subclass hook for the COLUMNWISE partial product; ``A_block``
        is 2-D and bounds-checked."""
        from ..utils.exceptions import UnsupportedError

        raise UnsupportedError(
            f"{self.sketch_type} has no columnwise partial-sketch rule; "
            "stream ROWWISE, or use a dense (JLT/CT), hash "
            "(CWT/SJLT/MMT/WZT), or RFT transform"
        )

    def apply_slice_kernel(self, A_block, start):
        """jit-safe COLUMNWISE partial: like the COLUMNWISE
        :meth:`apply_slice` but ``start`` may be a TRACED scalar (< 2^32
        — the counter-window offset contract) and the window may run
        past the sketch domain: out-of-domain operand entries are zeroed
        inside the kernel, so a zero-padded ``A_block`` contributes
        exactly the in-domain partial.  This is what lets the plan layer
        compile ONE executable per bucket that serves every ragged
        streaming batch.  No host-side bounds check (start is traced);
        implemented by the dense, hash, and RFT engines
        (``supports_slice_kernel``)."""
        from ..utils.exceptions import UnsupportedError

        raise UnsupportedError(
            f"{self.sketch_type} has no jit-safe slice kernel; planned "
            "streaming falls back to the eager apply_slice path"
        )

    def apply_slice_kernel_acc(self, acc, A_block, start):
        """One streaming chunk step as a single traced body:
        ``acc + apply_slice_kernel(A_block, start)`` cast to
        ``acc.dtype``.  This default composite is exactly what the plan
        layer always compiled; engines with a device-fused kernel (the
        hash sketches) override it to fold the accumulator add into the
        kernel's emit — REQUIRED to stay bitwise equal to this
        composite (a single IEEE add of the same partial), so the
        planned≡eager contract never depends on which path won."""
        part = self.apply_slice_kernel(A_block, start)
        return acc + part.astype(acc.dtype)

    def finalize_slices(self, acc, dim: Dimension | str = Dimension.COLUMNWISE):
        """Turn the merged COLUMNWISE slice-sum into the final sketch
        (identity for linear transforms; feature maps apply their
        pointwise epilogue here).  ROWWISE concatenations are already
        final and pass through unchanged."""
        return acc

    # -- loop-invariant operand hoisting ------------------------------------

    def _memoized_operand(self, key: str, build):
        """``build()`` memoized under ``key`` (sketches are immutable).
        Mid-trace calls skip the cache both ways: an operand built under
        a trace holds tracers and must not outlive it, and a cached
        concrete operand returned into a trace would be baked into the
        caller's executable as a constant.  Any array op under a trace
        yields a Tracer, so a scalar probe answers "am I being traced"."""
        if isinstance(jnp.zeros((), jnp.bool_), jax.core.Tracer):
            return build()
        cache = self.__dict__.setdefault("_hoist_cache", {})
        hit = cache.get(key)
        if hit is None:
            hit = cache[key] = build()
        return hit

    def hoistable_operands(self, dtype):
        """Counter-derived arrays the apply realizes that do NOT depend
        on the input (the sketch operand, shifts, ...), or None.

        XLA does not hoist this realization out of a ``lax.fori_loop``
        body even though it is loop-invariant — measured ~11 ms per
        8M-draw W per panel visit in the streaming-KRR sweep (round 3).
        Streaming consumers call this ONCE per jitted program (outside
        their panel loop) and pass the result to
        :meth:`apply_with_operands`.  Default: nothing to hoist.
        """
        return None

    def apply_with_operands(
        self, ops, A, dim: Dimension | str = Dimension.COLUMNWISE
    ):
        """Apply using pre-realized :meth:`hoistable_operands` (``ops``
        may be None → plain apply).  Default ignores ``ops``."""
        return self.apply(A, dim)

    # Convenience mirroring the python-skylark operator sugar
    # (python-skylark/skylark/sketch.py: __mul__ = columnwise, __div__ = rowwise).
    def __mul__(self, A):
        return self.apply(A, Dimension.COLUMNWISE)

    def __truediv__(self, A):
        return self.apply(A, Dimension.ROWWISE)

    # -- serialization ------------------------------------------------------

    def _param_dict(self) -> dict[str, Any]:
        return {}

    def to_dict(self) -> dict[str, Any]:
        """≙ ``sketch_transform_data_t::add_common`` + subclass fields."""
        d = {
            "skylark_object_type": "sketch",
            "skylark_version": SERIAL_VERSION,
            "sketch_type": self.sketch_type,
            "N": self.n,
            "S": self.s,
            "creation_context": self._creation_context.to_dict(),
        }
        d.update(self._param_dict())
        return d

    def to_json(self) -> str:
        return json.dumps(self.to_dict())

    # python-skylark compatibility surface (sketch.py:94-232).
    def serialize(self) -> dict[str, Any]:
        """≙ python-skylark ``serialize()`` (dict form of the transform)."""
        return self.to_dict()

    def getindim(self) -> int:
        """≙ python-skylark ``getindim()``."""
        return self.n

    def getsketchdim(self) -> int:
        """≙ python-skylark ``getsketchdim()``."""
        return self.s

    @classmethod
    def _from_param_dict(
        cls, d: dict[str, Any], context: SketchContext
    ) -> "SketchTransform":
        return cls(d["N"], d["S"], context)  # type: ignore[call-arg]

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "SketchTransform":
        ctx = SketchContext.from_dict(d["creation_context"])
        return cls._from_param_dict(d, ctx)

    @classmethod
    def from_json(cls, s: str) -> "SketchTransform":
        return cls.from_dict(json.loads(s))

    def __repr__(self):
        return f"{type(self).__name__}(N={self.n}, S={self.s})"


def from_dict(d: dict[str, Any]) -> SketchTransform:
    """Reconstruct any registered sketch from its dict (≙ from_ptree registry)."""
    t = d["sketch_type"]
    if t not in _REGISTRY:
        raise ValueError(
            f"unknown sketch_type {t!r}; known: {sorted(_REGISTRY)}"
        )
    if d.get("skylark_version", 1) < SERIAL_VERSION:
        import warnings

        warnings.warn(
            f"sketch serialized under stream revision "
            f"{d.get('skylark_version', 1)} (current {SERIAL_VERSION}): "
            "f32-uniform-derived values reproduce differently "
            "(docs/counter_contract.md, Stream revisions)",
            stacklevel=2,
        )
    return _REGISTRY[t].from_dict(d)


def from_json(s: str) -> SketchTransform:
    return from_dict(json.loads(s))


def deserialize_sketch(sketch_dict: dict[str, Any]) -> SketchTransform:
    """≙ python-skylark ``deserialize_sketch`` (sketch.py:33-42): rebuild a
    transform from its ``serialize()`` dict."""
    return from_dict(sketch_dict)


def create_sketch(
    sketch_type: str, n: int, s: int, context: SketchContext, **params: Any
) -> SketchTransform:
    """String-typed factory (≙ ``capi/csketch.cpp:15-58`` / ``create_sketch``)."""
    if sketch_type not in _REGISTRY:
        raise ValueError(
            f"unknown sketch_type {sketch_type!r}; known: {sorted(_REGISTRY)}"
        )
    return _REGISTRY[sketch_type](n, s, context=context, **params)
