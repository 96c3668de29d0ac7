"""PPT — Pham-Pagh TensorSketch for the polynomial kernel.

≙ ``sketch/PPT_data.hpp:24-90`` + ``sketch/PPT_Elemental.hpp:131-188``:
features for k(x, y) = (γ·xᵀy + c)^q via q CountSketches composed in the
FFT domain —

    Z(x) = IFFT( Π_{l<q} FFT( √γ·CWT_l(x) + √c·s_l·e_{h_l} ) )

where the ``√c·s_l·e_{h_l}`` term (one extra hashed coordinate per level,
``PPT_Elemental.hpp:165-166``) carries the additive constant of the
kernel.  The FFTs ride XLA's native complex FFT (TPU-supported); the
reference's explicit 1/S scaling + unnormalized c2r inverse collapse to
the normalized ``jnp.fft.ifft``.

Counter budget ≙ ``PPT_data_t::build``: q CWTs (2N each), then q hash
indices and q hash values.

TPU cost (round 3, v5e, 131072×4096→1024 q=3): the f32 FFT path runs
149 ms — ~50 ms in the three split-CWT matmuls, ~50 ms in the four c64
FFTs (~12-14 ms each, axis layout immaterial; measured), the rest in
complex products.  For **bf16** inputs the S-point DFT is instead done
as explicit MXU matmuls in real arithmetic (complex64 never
materializes; even as a full (S, S) cos/sin pair, ~1.4 ms per
half-transform vs 12.5 ms per FFT).  A real input's spectrum is
Hermitian, so S real numbers hold it: a forward transform is two
(S, S/2) products and the inverse two (S/2, S), S² multiply-adds a row
each.  A CountSketch has one ±1 a coordinate, so where its one-hot
operands exist (n·S ≤ ``_ONEHOT_LIMIT``) the forward tables take it in:
C_l·Hc is the (n, S/2) table of Hc's rows at the buckets, signed, and a
level's spectrum is X·[C_l·Hc | C_l·Hs], 2nS multiply-adds a row where
the hash and the transform took 2nS + S².  f32 keeps the
exact-precision FFT: a split-matmul
DFT needs ≥8 bf16 passes (data split3 × matrix split2 per real part) and
measures no faster than XLA's FFT.  ``jnp.fft.irfft`` is UNIMPLEMENTED
on the TPU backend (probed) — only full complex ``fft``/``ifft`` and the
real matmul-DFT are used.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import sparse as jsparse

from ..core.context import SketchContext
from ..core.random import sample
from ..core.sparse import RowSlots, row_slots
from .base import Dimension, SketchTransform, register_sketch
from .hash import CWT

__all__ = ["PPT"]

# bf16 matmul-DFT gate: a half-spectrum transform costs S²·m MXU
# multiply-adds per level (two (S, S/2) tables) vs ~6 HBM passes of
# (S, m) complex for the FFT; the matmul wins for S up to several
# thousand and batches wide enough to amortize building the four
# half-spectrum tables in-graph.
_DFT_MAX_S = 1 << 12
_DFT_MIN_BATCH = 4096
_DFT_MAX_Q = 8  # bf16 table rounding compounds ~linearly in q; see _dft_wins


@register_sketch
class PPT(SketchTransform):
    """TensorSketch feature map for the polynomial kernel (γ·xᵀy + c)^q."""

    sketch_type = "PPT"

    def __init__(
        self,
        n: int,
        s: int,
        context: SketchContext,
        q: int = 3,
        c: float = 1.0,
        gamma: float = 1.0,
    ):
        super().__init__(n, s, context)
        if q < 1:
            raise ValueError(f"PPT needs q >= 1, got {q}")
        self.q = int(q)
        self.c = float(c)
        self.gamma = float(gamma)
        self._seed = context.seed
        self._cwts = [CWT(n, s, context) for _ in range(self.q)]
        self._hidx_base = context.reserve(self.q)
        self._hval_base = context.reserve(self.q)

    def _hash_consts(self, dtype):
        idx = sample(
            "uniform_int", self._seed, self._hidx_base, self.q,
            dtype=jnp.int32, low=0, high=self.s - 1,
        )
        val = sample("rademacher", self._seed, self._hval_base, self.q, dtype=dtype)
        return idx, val

    def _dft_wins(self, dtype, batch: int) -> bool:
        """Gate for the bf16 matmul-DFT path (one predicate for both
        orientations — mirrors FastRFT._realize_wins).  TPU-only by
        default (v5e-measured crossover; CPU FFTs beat emulated bf16
        matmuls); ``SKYLARK_PPT_DFT=1`` forces it on for cross-backend
        tests, ``SKYLARK_NO_PPT_DFT=1`` forces it off."""
        if os.environ.get("SKYLARK_NO_PPT_DFT", "0") == "1":
            return False
        if (
            jax.default_backend() != "tpu"
            and os.environ.get("SKYLARK_PPT_DFT", "0") != "1"
        ):
            return False
        return (
            dtype == jnp.bfloat16
            and 2 <= self.s <= _DFT_MAX_S
            and batch >= _DFT_MIN_BATCH
            # Each of the q forward transforms + the inverse rounds its
            # tables to bf16 (~2^-8 relative per pass) and the
            # level products compound it, so worst-case feature error
            # grows ~linearly in q: measured ≤0.4% max-norm at q=3,
            # extrapolating past ~2% beyond q=8 — above the parity
            # tolerance.  High-degree kernels keep the exact FFT path.
            and self.q <= _DFT_MAX_Q
        )

    # -- loop-invariant operands ---------------------------------------------

    def _folds(self) -> bool:
        """Whether the bf16 DFT route folds each level's CountSketch into
        its forward tables: where the CountSketches have their one-hot
        operands (``HashSketch.hoistable_operands``, n·S ≤
        ``_ONEHOT_LIMIT``).  There a level's two folded tables hold the
        bytes of the sign matrix they replace, and its S × S transform
        is gone; above, the hash and the transform stay apart."""
        return self.n * self.s <= CWT._ONEHOT_LIMIT

    def hoistable_operands(self, dtype, *, sparse: bool = False):
        """What every apply realizes that does not depend on the input:
        each level's CountSketch operands (``HashSketch.
        hoistable_operands``; None where the tables fold them in), the
        constant's hashed coordinates and signs, and on the bf16 DFT
        route the tables (:meth:`_tables`), made once a program and held
        as buffers — outside a streaming consumer's panel loop, and
        never fused back into its transforms.  Memoized per dtype and
        route (``_memoized_operand``: skipped mid-trace).  The route is
        ``_dft_wins`` at the smallest batch that gate admits;
        :meth:`apply_with_operands` re-decides it by the real batch, as
        :meth:`apply` does, and ignores or builds the tables to match
        (a thin batch's CountSketches then build their own operands).
        ``sparse``: the operands for a sparse input (a BCOO or
        ``core.sparse.RowSlots``), which hashes its nonzeros' columns
        from their counters and takes the plain tables: no CountSketch
        operands, no folded tables."""
        dt = jnp.dtype(dtype)
        if dt.type not in (jnp.bfloat16, jnp.float32):
            return None
        dft = self._dft_wins(dt, _DFT_MIN_BATCH)
        fold = dft and self._folds() and not sparse

        def build():
            with jax.named_scope("ppt.hash"):
                cwt_ops = tuple(
                    None if fold or sparse else c.hoistable_operands(dt)
                    for c in self._cwts)
                consts = self._hash_consts(jnp.float32)
            tables = None
            if dft:
                # Behind the barrier the tables are buffers: without it the
                # TPU compiler fuses their cos and sin, or the folded rows'
                # gather, into every transform's convolution, where they
                # are made tile by tile.
                tables = jax.lax.optimization_barrier(self._tables(consts, fold))
            return cwt_ops, consts, tables

        key = f"{dt.name}/{dft}" + ("/sparse" if sparse else "")
        return self._memoized_operand(key, build)

    def apply_with_operands(
        self, ops, A, dim: Dimension | str = Dimension.COLUMNWISE
    ):
        """:meth:`apply` with the :meth:`hoistable_operands` ``ops``
        (None: built here): the same route by the same gate, the same
        bits."""
        return self._apply(A, dim, ops)

    def _operands(self, ops, dft: bool, fold: bool):
        """(CWT operands, (idx, val), tables) from ``ops``, what is
        missing built here; ``val`` is f32 (±1: exact in every dtype).
        ``fold``: whether the DFT route takes the folded tables; operands
        hoisted for the other kind of input are refused."""
        cwt_ops, consts, tables = ops or ((None,) * self.q, None, None)
        if consts is None:
            with jax.named_scope("ppt.hash"):
                consts = self._hash_consts(jnp.float32)
        if dft and tables is None:
            tables = self._tables(consts, fold)
        elif dft and len(tables) != (5 if fold else 3):
            raise ValueError(
                "operands hoisted for a " + ("sparse" if fold else "dense") + " input; "
                f"take hoistable_operands(dtype, sparse={not fold})")
        return cwt_ops, consts, tables

    def _countsketch(self, l, X, cwt_ops, dim):
        """Level l's CountSketch of X: (S, m) columnwise, (m, S) rowwise,
        in X's dtype.  A sparse X lands in dense bins, never densified
        (``HashSketch``'s dense output: rows in slots hash each slot's
        column from its counters)."""
        if _is_sparse(X):
            return self._cwts[l].apply(X, dim, dense_output=True)
        return self._cwts[l].apply_with_operands(cwt_ops[l], X, dim)

    def _features(self, X, ops=None, rowwise: bool = False):
        """Columnwise features for X (n, m) → (S, m) real.  ``rowwise``:
        X is a sparse (m, n), each level's CountSketch made rowwise and
        transposed."""
        dtype = X.dtype
        if not rowwise and self._dft_wins(dtype, X.shape[1]):
            return self._features_dft(X, ops=ops)
        cwt_ops, (idx, val), _ = self._operands(ops, False, False)
        sqrt_g = jnp.asarray(np.sqrt(self.gamma), dtype)
        sqrt_c = jnp.asarray(np.sqrt(self.c), dtype)
        hash_scope = "ppt.scatter" if _is_sparse(X) else "ppt.hash"
        dim = Dimension.ROWWISE if rowwise else Dimension.COLUMNWISE
        # Seed the frequency-domain product with level 0 (one multiply —
        # and one eager complex-ones allocation — fewer than starting
        # from ones).
        P = None
        for l in range(self.q):
            with jax.named_scope(hash_scope):
                W = sqrt_g * self._countsketch(l, X, cwt_ops, dim)
                W = W.T if rowwise else W
                W = W.at[idx[l], :].add(sqrt_c * val[l].astype(dtype))
            with jax.named_scope("ppt.dft"):
                F = jnp.fft.fft(W, axis=0)
            if P is None:
                P = F
            else:
                with jax.named_scope("ppt.product"):
                    P = P * F
        with jax.named_scope("ppt.inverse"):
            return jnp.real(jnp.fft.ifft(P, axis=0)).astype(dtype)

    # -- bf16 matmul-DFT fast path (TPU) -----------------------------------

    def _dft_tables(self):
        """(Hc, Hs, G): the half-spectrum DFT tables in bf16, built
        in-graph from one cosine and one sine of the (S, h) angles
        2πjk/S, k < h = ⌈S/2⌉.  A real input's spectrum is Hermitian,
        F[S−k] = conj F[k], so S real numbers hold it:

        * ``Hc`` (S, h), cos 2πjk/S: the real parts of frequencies 0…h−1;
        * ``Hs`` (S, h), −sin 2πjk/S: their imaginary parts, column 0
          (frequency 0's, identically zero) carrying the real Nyquist
          term's (−1)^j for even S, zero for odd S (no Nyquist term);
        * ``G`` (2, h, S): the inverse, S · z = Re·G[0] + Im·G[1] —
          ``Hc``ᵀ and ``Hs``ᵀ with row k times 2 for a conjugate pair
          (row 0, frequency 0 and the Nyquist term, once).

        Every entry is a bf16 cosine or sine, or twice one.  The
        index product j·k stays below 2^24 for S ≤ 2^12 (int32-exact,
        reduced mod S before the float conversion)."""
        s, h = self.s, -(-self.s // 2)
        with jax.named_scope("ppt.dft"):
            j = jnp.arange(s, dtype=jnp.int32)[:, None]
            k = jnp.arange(h, dtype=jnp.int32)[None, :]
            theta = jnp.float32(2.0 * np.pi / s) * ((j * k) % jnp.int32(s)).astype(jnp.float32)
            nyquist = (1 - 2 * (j % 2)) * (1 - s % 2)
            Hc = jnp.cos(theta).astype(jnp.bfloat16)
            Hs = jnp.where(k == 0, nyquist, -jnp.sin(theta)).astype(jnp.bfloat16)
            # made once: unbarred, the compiler makes the cosine and the
            # sine again in the fusion that transposes them
            Hc, Hs = jax.lax.optimization_barrier((Hc, Hs))
            w = jnp.where(k == 0, 1, 2).astype(jnp.bfloat16)
            return Hc, Hs, jnp.stack([(Hc * w).T, (Hs * w).T])

    def _tables(self, consts, fold: bool | None = None):
        """The bf16 DFT route's tables: :meth:`_dft_tables`' ``(Hc, Hs,
        G)``, or where ``fold`` (by default :meth:`_folds`; a sparse input
        never folds) ``(Tc, Ts, Rc, Rs, G)``, each
        level's CountSketch (buckets b_l, signs v_l) and the constant's
        coordinate (h_l, s_l of ``consts``) taken into its forward
        tables:

        * ``Tc[l]``, ``Ts[l]`` (n, h) bf16: v_l ⊙ Hc[b_l] and v_l ⊙
          Hs[b_l], the rows of Hc and Hs at the level's buckets, signed
          (exact: a signed bf16 entry), so X·Tc[l] is (X·C_l)·Hc summed
          in f32;
        * ``Rc``, ``Rs`` (q, h) f32: the constant's spectrum,
          √c·s_l·Hc[h_l] and √c·s_l·Hs[h_l].

        The rows' gathers and signs are ``ppt.hash``'s."""
        Hc, Hs, G = self._dft_tables()
        if not (self._folds() if fold is None else fold):
            return Hc, Hs, G
        idx, val = consts
        with jax.named_scope("ppt.hash"):
            signed = [(c.buckets(), c.values(jnp.bfloat16)[:, None]) for c in self._cwts]
            Tc = tuple(v * Hc[b] for b, v in signed)
            Ts = tuple(v * Hs[b] for b, v in signed)
            r = jnp.float32(np.sqrt(self.c)) * val[:, None]
            Rc = r * Hc[idx].astype(jnp.float32)
            Rs = r * Hs[idx].astype(jnp.float32)
        return Tc, Ts, Rc, Rs, G

    def _features_dft(self, X, rowwise: bool = False, ops=None):
        """bf16 features via explicit real-arithmetic DFT matmuls on the
        half spectrum (:meth:`_tables`): each level's S-point transform
        is two (S, h) MXU matmuls — or, where the tables fold the
        CountSketch in, two (n, h) matmuls of X itself, √γ and the
        constant's spectrum added to the f32 products — the level
        products run on (Re, Im) f32 pairs of h columns, and the inverse
        is one matmul contracting the stacked (2, h) spectrum — one
        (S, S) matmul's work, half the full spectrum's; complex64 never
        materializes.
        Values match the FFT path to bf16 feature accuracy (the DFT
        tables round to bf16; inputs are already bf16).  ``rowwise``
        keeps the batch on the major axis ((m, S) layout, transform on
        the minor axis) so rowwise applies skip two full-batch
        transposes: each product contracts the tables' leading axes with
        the n, S (or frequency) axes of either layout.  A sparse X
        (:meth:`_apply_sparse`) takes the plain tables: each level's
        CountSketch lands in dense bins (``ppt.scatter``) and is
        transformed as a dense input's is."""
        sparse = _is_sparse(X)
        fold = self._folds() and not sparse
        cwt_ops, (idx, val), tables = self._operands(ops, True, fold)
        if fold:
            Tc, Ts, Rc, Rs, G = tables
        else:
            Hc, Hs, G = tables
        sqrt_c = jnp.asarray(np.sqrt(self.c), jnp.float32)
        dim = Dimension.ROWWISE if rowwise else Dimension.COLUMNWISE
        ax = 1 if rowwise else 0  # the n, S (then frequency) axis

        def mm(W, M):
            # Contracts W's axis ``ax`` with the table's axis 0,
            # preserving W's layout.
            args, dims = ((W, M), ((1,), (0,))) if rowwise else ((M, W), ((0,), (0,)))
            return jax.lax.dot_general(
                *args, (dims, ((), ())), preferred_element_type=jnp.float32)

        def spectrum(l):
            """Level l's (Re, Im) half spectrum, f32."""
            if fold:
                sqrt_g = jnp.asarray(np.sqrt(self.gamma), jnp.float32)
                with jax.named_scope("ppt.dft"):
                    # the constant's spectrum, the same for every input
                    cr, ci = (R[l][None, :] if rowwise else R[l][:, None] for R in (Rc, Rs))
                    return sqrt_g * mm(X, Tc[l]) + cr, sqrt_g * mm(X, Ts[l]) + ci
            sqrt_g = jnp.asarray(np.sqrt(self.gamma), jnp.bfloat16)
            loc = (slice(None), idx[l]) if rowwise else (idx[l], slice(None))
            with jax.named_scope("ppt.scatter" if sparse else "ppt.hash"):
                # (m, S) rowwise / (S, m) columnwise
                W = sqrt_g * self._countsketch(l, X, cwt_ops, dim)
                Wb = W.astype(jnp.float32).at[loc].add(sqrt_c * val[l]).astype(jnp.bfloat16)
            with jax.named_scope("ppt.dft"):
                return mm(Wb, Hc), mm(Wb, Hs)

        Pr = Pi = None
        for l in range(self.q):
            Re, Im = spectrum(l)
            if Pr is None:
                Pr, Pi = Re, Im
            else:
                with jax.named_scope("ppt.product"):
                    # column 0 holds two reals, frequency 0 and Nyquist
                    real = jax.lax.broadcasted_iota(jnp.int32, Re.shape, ax) == 0
                    Pr, Pi = (Pr * Re - jnp.where(real, 0.0, Pi * Im),
                              jnp.where(real, Pi * Im, Pr * Im + Pi * Re))
        # ifft real part: [Pr | Pi]/S · G, the conjugate halves in G; 1/S
        # before the bf16 rounding (exact for S a power of two), where it
        # fuses into the product.  Stacked on a major axis, the halves are
        # written in place and read by one product of K = 2h.
        with jax.named_scope("ppt.inverse"):
            r = jnp.float32(1.0 / self.s)
            P = jnp.stack([(Pr * r).astype(jnp.bfloat16), (Pi * r).astype(jnp.bfloat16)])
            args, dims = ((P, G), ((0, 2), (0, 1))) if rowwise else ((G, P), ((0, 1), (0, 1)))
            Z = jax.lax.dot_general(
                *args, (dims, ((), ())), preferred_element_type=jnp.float32)
            return Z.astype(jnp.bfloat16)

    def apply(self, A, dim: Dimension | str = Dimension.COLUMNWISE):
        return self._apply(A, dim, None)

    def _apply(self, A, dim, ops):
        dim = Dimension.of(dim)
        if _is_sparse(A):
            return self._apply_sparse(A, dim, ops)
        A = jnp.asarray(A)
        dtype = A.dtype if jnp.issubdtype(A.dtype, jnp.floating) else jnp.float32
        A = A.astype(dtype)
        squeeze = A.ndim == 1
        if dim is Dimension.COLUMNWISE:
            X = A[:, None] if squeeze else A
            if X.shape[0] != self.n:
                raise ValueError(f"columnwise apply needs {self.n} rows, got {A.shape}")
            Z = self._features(X, ops)
            return Z[:, 0] if squeeze else Z
        X = A[None, :] if squeeze else A
        if X.shape[-1] != self.n:
            raise ValueError(f"rowwise apply needs {self.n} cols, got {A.shape}")
        if not squeeze and self._dft_wins(dtype, X.shape[0]):
            return self._features_dft(X, rowwise=True, ops=ops)
        Z = self._features(X.T, ops)
        return Z.T if not squeeze else Z[:, 0]

    def _apply_sparse(self, A, dim, ops):
        """Features of a two-dimensional BCOO or of rows in slots
        (``core.sparse.RowSlots``, rowwise), never densified: on either
        route each level's CountSketch lands in dense (S, batch) or
        (batch, S) bins, ``HashSketch``'s dense output under the scope
        ``ppt.scatter``, and the bins are transformed as a dense input's
        CountSketch is (the DFT route with its plain tables: the folded
        ones would be rows gathered by column).  Rowwise, a concrete
        BCOO's rows are laid out in slots once for all levels.  The route
        is the dense one's gate, ``_dft_wins`` on the dtype and the
        batch."""
        if A.ndim != 2:
            raise ValueError(f"PPT applies to a two-dimensional BCOO, got {A.shape}")
        dtype = A.dtype if jnp.issubdtype(A.dtype, jnp.floating) else jnp.float32
        A = A.astype(dtype)
        if dim is Dimension.COLUMNWISE:
            if isinstance(A, RowSlots):
                raise ValueError("row slots are sketched rowwise")
            if A.shape[0] != self.n:
                raise ValueError(f"columnwise apply needs {self.n} rows, got {A.shape}")
            return self._features(A, ops)
        if A.shape[1] != self.n:
            raise ValueError(f"rowwise apply needs {self.n} cols, got {A.shape}")
        if isinstance(A, jsparse.BCOO) and not isinstance(A.data, jax.core.Tracer):
            A = row_slots(A)
        if self._dft_wins(dtype, A.shape[0]):
            return self._features_dft(A, rowwise=True, ops=ops)
        return self._features(A, ops, rowwise=True).T

    def _param_dict(self):
        return {"q": self.q, "c": self.c, "gamma": self.gamma}

    @classmethod
    def _from_param_dict(cls, d, context):
        return cls(
            d["N"], d["S"], context, q=d["q"], c=d["c"], gamma=d["gamma"]
        )


def _is_sparse(X) -> bool:
    return isinstance(X, (jsparse.BCOO, RowSlots))
