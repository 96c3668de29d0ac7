"""Compile the main path's kernels and programs for a described v5e chip.

No chip is attached: the TPU compiler installed here compiles for a
topology that is described (``v5e:2x2``, first device), and raises what
the chip's compiler would raise — block shapes off the (8, 128) grain,
scoped-VMEM overflow, loads Mosaic cannot prove aligned.  Interpret-mode
tests cannot see any of that.  Nothing runs, so nothing here says
anything about results or times.

Every Pallas kernel a default route can pick on a TPU is compiled at its
bench shape, and the text must hold a ``tpu_custom_call``.

The topology is described inside a module-scoped fixture (never at
import: only one process may load the TPU library, and every xdist
worker imports every test file), compiles run in the test's own
process, and the persistent compilation cache is off around them.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from libskylark_tpu import SketchContext
from libskylark_tpu.sketch import CWT, FJLT, JLT, SJLT
from libskylark_tpu.sketch import fjlt as fjlt_mod
from libskylark_tpu.sketch import hash as hash_mod
from libskylark_tpu.sketch import pallas_fut, pallas_window

K, M, S = 131_072, 4096, 1024  # the bench's sketch shape
F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch):
    """Static gates ask ``jax.default_backend()``; steer them to the
    branch they take on the chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for k in ("SKYLARK_PALLAS_WINDOW", "SKYLARK_PALLAS_GATHER",
              "SKYLARK_NO_PALLAS"):
        monkeypatch.delenv(k, raising=False)


def _compile(fn, one_chip, *shapes):
    """Lower and compile for the described chip, x64 OFF as on the chip
    (the suite's conftest turns it on; under it index maps and loop
    counters trace as i64, which is not the program users run)."""
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    with jax.enable_x64(False):
        return jax.jit(fn).lower(*args).compile()


def _text(fn, one_chip, *shapes):
    return _compile(fn, one_chip, *shapes).as_text()


# -- window scatter (CWT/MMT/SJLT columnwise slices) --------------------------


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("with_acc", [False, True], ids=["plain", "acc"])
def test_window_scatter(one_chip, dtype, with_acc):
    if with_acc:
        def fn(A, b, v, acc):
            return pallas_window.scatter_rows(A, b, v, S, acc=acc)
        extra = [((S, M), F32)]
    else:
        def fn(A, b, v):
            return pallas_window.scatter_rows(A, b, v, S)
        extra = []
    assert pallas_window.supported(K, S, M)
    text = _text(fn, one_chip, ((K, M), dtype), ((K,), I32), ((K,), F32),
                 *extra)
    assert "tpu_custom_call" in text


def test_window_scatter_sjlt_nnz4(one_chip):
    assert pallas_window.supported(K, S, M, 4)
    text = _text(
        lambda A, b, v: pallas_window.scatter_rows(A, b, v, S),
        one_chip, ((K, M), F32), ((4, K), I32), ((4, K), F32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "k,s,m,nnz", [(16_384, 1000, 320, 1), (16_384, 1000, 320, 4),
                  (65_536, 1024, 256, 1)],
    ids=["self_check", "self_check_nnz4", "hw_guard"],
)
def test_window_scatter_check_shapes(one_chip, k, s, m, nnz):
    """The off-tile shapes ``chip_smoke.py``'s self-checks run."""
    text = _text(
        lambda A, b, v: pallas_window.scatter_rows(A, b, v, s),
        one_chip, ((k, m), F32), ((nnz, k), I32), ((nnz, k), F32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("cls,nnz", [(CWT, 1), (SJLT, 4)],
                         ids=["CWT", "SJLT4"])
def test_fused_stream_chunk_step_routes_to_kernel(one_chip, as_tpu, cls, nnz):
    """The streaming chunk step as the plan layer traces it
    (``apply_slice_kernel_acc``, traced start): on a TPU the static gate
    says kernel, and the compiled step holds it."""
    assert hash_mod._window_mode(K, M, S, F32, nnz) == "kernel"
    kw = {"nnz": nnz} if nnz > 1 else {}
    sk = cls(4 * K, S, SketchContext(seed=3), **kw)
    text = _text(
        lambda acc, blk, start: sk.apply_slice_kernel_acc(acc, blk, start),
        one_chip, ((S, M), F32), ((K, M), F32), ((), I32),
    )
    assert "tpu_custom_call" in text


# -- FUT / FJLT ---------------------------------------------------------------


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_rfut_rowwise(one_chip, dtype):
    assert pallas_fut.supported(K, M, M)
    text = _text(
        lambda x, d: pallas_fut.rfut_rowwise(x, d, M),
        one_chip, ((K, M), dtype), ((M,), dtype),
    )
    assert "tpu_custom_call" in text


def test_rfut_rowwise_guard_shape(one_chip):
    text = _text(
        lambda x, d: pallas_fut.rfut_rowwise(x, d, 512),
        one_chip, ((256, 512), F32), ((512,), F32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_fjlt_apply_pallas_is_one_kernel_then_xla_gather(one_chip, dtype):
    """The kernel branch of the rowwise FJLT: the fused D·x → WHT kernel
    writes (m, NB), and XLA gathers the S sampled lanes."""
    sk = FJLT(M, S, SketchContext(seed=5))
    text = _text(sk._apply_pallas, one_chip, ((256, M), dtype))
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert "gather(" in text


def test_gather_scaled_rows(one_chip, as_tpu):
    nrows, s, m = 2048, 1024, M
    assert pallas_window.supported_gather(nrows, s, m)
    assert fjlt_mod._gather_mode(nrows, s, m, F32) == "kernel"
    text = _text(
        lambda T, i: pallas_window.gather_scaled_rows(T, i, 0.5),
        one_chip, ((nrows, m), F32), ((s,), I32),
    )
    assert "tpu_custom_call" in text


def test_gather_self_check_shape(one_chip):
    text = _text(
        lambda T, i: pallas_window.gather_scaled_rows(T, i, 0.3125),
        one_chip, ((3000, 320), F32), ((4096,), I32),
    )
    assert "tpu_custom_call" in text


# -- whole programs at full size ----------------------------------------------


def test_jlt_apply_full_size(one_chip):
    sk = JLT(M, S, SketchContext(seed=7))
    text = _text(lambda A: sk.apply(A, "rowwise"), one_chip,
                    ((262_144, M), BF16))
    assert "tpu_custom_call" not in text  # a plain MXU matmul


def _krr_rows(start, rows, X):
    """A ``block_fn`` that lives with its module: the streamed trainer's
    shared chunk programs, the ones the benchmark's cell runs."""
    return jax.lax.dynamic_slice_in_dim(X, start, rows, axis=0)


def test_streaming_krr_sweep_step_full_size(one_chip):
    """One sweep step (``zr``) of the streaming-KRR chunk programs at
    the north-star panel: 131072x4096 -> 2048 bf16, 8 panels."""
    from libskylark_tpu.ml import GaussianKernel
    from libskylark_tpu.ml.krr import streaming_krr_chunk_programs

    D, SZ, NB, BR = 4096, 2048, 8, 131_072
    maps = [GaussianKernel(D, sigma=8.0).create_rft(
        SZ, "regular", SketchContext(seed=9))]

    def block_fn(start, rows, X0):
        return jnp.roll(X0, start // rows, axis=0)

    _, zr, _ = streaming_krr_chunk_programs(
        maps, 0, NB, BR, block_fn, BF16
    )
    compiled = _compile(zr, one_chip, ((), F32),
                        ((NB, BR, 1), F32), ((SZ, 1), F32), ((BR, D), BF16))
    assert compiled.memory_analysis().temp_size_in_bytes < 12 << 30


@pytest.mark.parametrize("program", ["gram", "zr", "apply_delta"])
def test_streaming_krr_feature_pass_is_one_output_fusion(one_chip, program):
    """The bf16 feature pass of each chunk program (8192 x 784 -> 1024, the
    bench's K): the product and the turns epilogue are ONE ``kOutput``
    fusion whose convolution yields f32, so no f32 panel lands in HBM
    and the phase is never rounded to bf16; no cosine at the panel's
    shape is left (W's Box-Muller draw keeps its own, at W's)."""
    from libskylark_tpu.ml import GaussianKernel
    from libskylark_tpu.ml.krr import streaming_krr_chunk_programs

    D, SZ, NB, BR, T = 784, 1024, 2, 8192, 10
    maps = [GaussianKernel(D, sigma=28.0).create_rft(
        SZ, "regular", SketchContext(seed=9))]

    progs = streaming_krr_chunk_programs(maps, 0, NB, BR, _krr_rows, BF16)
    progs = dict(zip(("gram", "zr", "apply_delta"), progs))
    lam, X, R, W = ((), F32), ((NB * BR, D), BF16), ((NB, BR, T), F32), ((SZ, T), F32)
    shapes = {"gram": (lam, X), "zr": (lam, R, W, X), "apply_delta": (R, W, X)}
    text = _text(progs[program], one_chip, *shapes[program])

    panel = rf"\[{BR},{SZ}\]"
    fused = [b for b in text.split("\n\n")  # a computation a paragraph
             if re.search(rf"= f32{panel}\S* convolution\(", b)]
    assert len(fused) == 1, [b[:80] for b in fused]
    body = fused[0]
    assert "rft.epilogue.turns" in body  # the named scope rides in the metadata
    assert re.search(rf"f32{panel}\S* floor\(", body)
    assert not re.search(rf"{panel}\S* cosine\(", text)
    name = re.match(r"%?(\S+)", body).group(1)
    call = re.search(rf"fusion\([^\n]*kind=(\w+), calls=%?{re.escape(name)}\b", text)
    assert call and call.group(1) == "kOutput"


_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(([^)]*)\)(.*)$")


def _readers_of_values_made(text, dtype, elems, scope):
    """``{value: [its readers]}`` for every ``dtype`` value of ``elems``
    elements that a fusion under the named ``scope`` makes, in any
    computation of a compiled program's text: a reader is an
    instruction of the same computation that has the value, or a
    bitcast of it, among its operands."""
    found = {}
    for computation in text.split("\n\n"):
        inst = {}
        for line in computation.split("\n"):
            m = _INSTRUCTION.match(line)
            if m:
                name, dt, dims, op, operands, rest = m.groups()
                size = math.prod(int(d) for d in dims.split(",") if d)
                inst[name] = (dt, size, op, re.findall(r"%([^\s,]+)", operands), rest)

        def readers(value):
            out = []
            for name, (_, _, op, operands, _) in inst.items():
                if value in operands:
                    out += readers(name) if op == "bitcast" else [name]
            return out

        for name, (dt, size, op, _, rest) in inst.items():
            if (dt, size, op) == (dtype, elems, "fusion") and scope in rest:
                found[name] = readers(name)
    return found


def test_block_admm_remade_iterate_holds_one_block_at_full_size(one_chip):
    """The BlockADMM iteration with its feature blocks remade, at the
    benchmark cell's size (2,097,152 x 784 bf16 rows, 4 blocks of 1024
    features, 10 classes): all four blocks are 17.2 GB and one is 4.3,
    so the program fits only while one is live at a time.  Its
    operations carry the four stage scopes, and the f32 operands of the
    thin products reach the bf16 blocks as ``reduce-precision`` pieces
    (a cast there and back is folded away by this compiler).  Each block
    is read twice (the right-hand side's product; the objective's and
    o_j's in one), where the recurrence's four products read it four
    times (PR 34)."""
    from libskylark_tpu.ml import GaussianKernel, admm

    N, D, SJ, J, K_ = 2**21, 784, 1024, 4, 10
    ctx = SketchContext(seed=9)
    maps = [GaussianKernel(D, sigma=28.0).create_rft(SJ, "regular", ctx)
            for _ in range(J)]
    spec = admm._Spec(loss="hinge", reg="l2", maps=admm._Maps(maps), P=1,
                      scale_maps=False, cached=False, rho=1.0, lam=0.01)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    small, tall = shaped((J * SJ, K_), F32), shaped((1, K_, N), F32)
    per = shaped((1, J * SJ, K_), F32)
    state = (small,) * 3 + (tall,) * 4 + (per,) * 2 + (shaped((), F32),)
    square = [shaped((1, SJ, SJ), F32)] * J  # the factors, and the Gram matrices
    with jax.enable_x64(False):
        compiled = admm.admm_iterate.lower(
            state, shaped((N, D), BF16), square, square,
            shaped((1, N), F32), spec=spec, maxiter=2).compile()
    block = 2 * N * SJ
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert block < temp < 2 * block, temp / 1e9
    text = compiled.as_text()
    for scope in ("admm.features", "admm.thin_products", "admm.prox",
                  "admm.block_solve"):
        assert scope in text, scope
    assert "reduce-precision" in text
    assert "rft.epilogue.turns" in text
    reads = _readers_of_values_made(text, "bf16", N * SJ, "admm.features")
    assert len(reads) == J, reads  # one making of each block, in the loop body
    assert all(len(r) == 2 for r in reads.values()), reads


# -- the named scopes are metadata: the programs stay the programs ------------
#
# ``benchmarks/scope_reduce.py`` reads device time by the named scopes on
# the compiled instructions (PR 35).  The scopes must be there, and
# opening them must not have moved an instruction: the counts below were
# read from the parent commit's programs, compiled the same way.


def _counts(text):
    return (len(re.findall(r" fusion\(", text)),
            len(re.findall(r" convolution\(", text)))


def _scopes_that_own_an_operation(text):
    """The dotted scope names that some operation's time would be put
    down to: ``profiling.hlo_scopes`` under the one-operation-one-scope
    rule of ``benchmarks/scope_reduce.py`` (a fusion's first product)."""
    import sys

    from libskylark_tpu.utils import profiling

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks"))
    import scope_reduce

    dotted = re.compile(r"^[a-z_]+\.[a-z_.]+$")
    return {scope_reduce.scope_of(scope_reduce.owner(entry), dotted)
            for entry in profiling.hlo_scopes(text).values()} - {None}


def test_block_admm_iterate_carries_its_scopes_and_the_parents_instructions(one_chip):
    from libskylark_tpu.ml import GaussianKernel, admm

    N, D, SJ, J, K_ = 2**21, 784, 1024, 4, 10
    ctx = SketchContext(seed=9)
    maps = [GaussianKernel(D, sigma=28.0).create_rft(SJ, "regular", ctx)
            for _ in range(J)]
    spec = admm._Spec(loss="hinge", reg="l2", maps=admm._Maps(maps), P=1,
                      scale_maps=False, cached=False, rho=1.0, lam=0.01)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    small, tall = shaped((J * SJ, K_), F32), shaped((1, K_, N), F32)
    per = shaped((1, J * SJ, K_), F32)
    state = (small,) * 3 + (tall,) * 4 + (per,) * 2 + (shaped((), F32),)
    square = [shaped((1, SJ, SJ), F32)] * J
    with jax.enable_x64(False):
        compiled = admm.admm_iterate.lower(
            state, shaped((N, D), BF16), square, square,
            shaped((1, N), F32), spec=spec, maxiter=2).compile()
    text = compiled.as_text()
    assert _counts(text) == (555, 136)  # the parent's (commit ce4deed)
    assert compiled.memory_analysis().temp_size_in_bytes == 5_267_843_072
    for scope in ("admm.features", "admm.thin_products", "admm.prox",
                  "admm.block_solve", "admm.tail", "rft.epilogue.turns"):
        assert scope in text, scope
    # the prox is fused into the products: it owns next to nothing
    assert {"admm.features", "admm.thin_products", "admm.block_solve",
            "admm.tail"} <= _scopes_that_own_an_operation(text)


@pytest.mark.parametrize("program,product,counts", [
    ("gram", "krr.gram_product", (10, 2)),
    # one more than the parent's 9: with λ an operand the epilogue
    # ``acc - λ·Wc`` is a fusion (λ = 1 a constant folded it to a subtract)
    ("zr", "krr.zr_product", (10, 2)),
    ("apply_delta", "krr.delta_product", (9, 2)),
])
def test_streaming_krr_programs_carry_their_scopes_and_the_parents_instructions(
        one_chip, program, product, counts):
    from libskylark_tpu.ml import GaussianKernel
    from libskylark_tpu.ml.krr import streaming_krr_chunk_programs

    D, SZ, NB, BR, T = 784, 1024, 2, 8192, 10
    maps = [GaussianKernel(D, sigma=28.0).create_rft(
        SZ, "regular", SketchContext(seed=9))]

    progs = streaming_krr_chunk_programs(maps, 0, NB, BR, _krr_rows, BF16)
    progs = dict(zip(("gram", "zr", "apply_delta"), progs))
    lam, X, R, W = ((), F32), ((NB * BR, D), BF16), ((NB, BR, T), F32), ((SZ, T), F32)
    shapes = {"gram": (lam, X), "zr": (lam, R, W, X), "apply_delta": (R, W, X)}
    text = _text(progs[program], one_chip, *shapes[program])
    assert _counts(text) == counts  # the loops' are the parent's (commit 9c37271)
    # the turns epilogue nests under the feature pass
    assert re.search(r'op_name="[^"]*krr\.features/[^"]*rft\.epilogue\.turns', text)
    assert {"krr.features", product} <= _scopes_that_own_an_operation(text)


# -- the sparse-times-panel product: no nnz x s buffer ------------------------


def _prepared_shapes(n, nnz, one_chip, hot_share=0.0, pad=1.1):
    """A ``core.sparse.Prepared`` of abstract arrays at the size of an
    ``n``-vertex graph with ``nnz`` nonzeros: the column blocks and
    bucket counts ``prepare`` gives a graph of that size (``pad`` slots
    a nonzero; rows spread over a dozen piece counts), with
    ``hot_share`` of the slots in a hot table before them where that is
    given."""
    from libskylark_tpu.core import sparse

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    blocks = -(-n // sparse.TABLE_ROWS)
    counts = [c for c in sparse._counts(4096) if c >= 2][:24]
    rows = [n // len(counts)] * len(counts)
    rows[0] += n - sum(rows)

    def buckets_of(nonzeros):
        want = pad * nonzeros / sparse.PIECE                # pieces a table
        scale = want / sum(r * k for r, k in zip(rows, counts))
        return tuple((r, max(int(round(k * scale)), 1)) for r, k in zip(rows, counts))

    hot = sparse.HOT_ROWS if hot_share else 0
    buckets = ((buckets_of(hot_share * nnz),) if hot else ()) + (
        buckets_of((1 - hot_share) * nnz / blocks),) * blocks
    pieces = [sum(r * k for r, k in b) for b in buckets]
    return sparse.Prepared(
        cols=tuple(shaped((sparse.PIECE, p), I32) for p in pieces),
        vals=tuple(shaped((sparse.PIECE, p), F32) for p in pieces),
        place=(shaped((n,), I32),) * len(pieces), hot=shaped((hot,), I32),
        shape=(n, n), nse=nnz, hot_nse=int(hot_share * nnz), buckets=buckets,
        symmetric=True)


# the cell's layout on the chip (PERF.md section 6, PR 38): 63.2, 50.2 and
# 50.3 M slots for 140.67 M nonzeros, 0.392 of them in the hot table
CELL_LAYOUT = dict(hot_share=0.386, pad=1.164)


def _cell_sizes():
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs", "graph_se_orkut_f32.json")) as f:
        z = json.load(f)
    return z["vertices"], 2 * z["edges"], z["s"]


def _sweep_segment(A, one_chip):
    """``linalg.svd._chunk`` lowered for two sweeps with ``A`` at s = 16."""
    from libskylark_tpu.linalg import svd

    Y = jax.ShapeDtypeStruct((A.shape[0], 16), F32, sharding=one_chip)
    st = dict(it=jax.ShapeDtypeStruct((), I32, sharding=one_chip), Y=Y)
    with jax.enable_x64(False):
        return svd._chunk.lower(st, A, 2, 2, orthogonalize=True)


def test_sparse_product_holds_a_chunk_of_gathered_rows_and_no_more(one_chip):
    """The sweep segment of ``approximate_ase`` at the benchmark cell's
    size (``graph_se_orkut_f32``: its vertices, twice its edges, s = 16,
    the columns in a hot table of 131,072 rows and two of 921,733, with
    63 M, 50 M and 50 M slots): ``bcoo_dot_general`` would hold every
    nonzero's row of the panel at once (nnz x s x 4 bytes, gigabytes);
    the chunked product holds a chunk's gathered rows, a table's
    pieces' sums (an s-row for every eight slots: what still grows with
    nnz; the hot table's are the largest, a fifth under a column block's
    before there was a hot table) and the panels."""
    from libskylark_tpu.core import sparse

    n, nnz, s = _cell_sizes()
    A = _prepared_shapes(n, nnz, one_chip, **CELL_LAYOUT)
    assert A.tables == 3 and A.hot.shape == (sparse.HOT_ROWS,) == (131072,)
    assert sparse._table(n) == 921733 and s == 16
    slots = [c.shape[1] * sparse.PIECE for c in A.cols]
    assert all(abs(x / on_chip - 1) < 0.05 for x, on_chip in zip(slots, (63.2e6, 50.2e6, 50.3e6)))
    compiled = _sweep_segment(A, one_chip).compile()
    mem = compiled.memory_analysis()
    panel = n * s * 4
    pieces = max(slots) // sparse.PIECE * s * 4              # a table's pieces' sums
    before = 1.1 * nnz / 2 / sparse.PIECE * s * 4            # a column block's, two tables
    chunk = sparse.CHUNK_BYTES                               # a step's gathered rows
    everything = nnz * s * 4                                 # what the product is for
    assert pieces < 0.85 * before
    assert mem.temp_size_in_bytes < pieces + 10 * chunk + 4 * panel < everything / 3
    assert mem.argument_size_in_bytes < 1.2 * nnz * 8 + n * 4 * A.tables + 2 * panel
    text = compiled.as_text()
    assert "sparse.product" in text and "svd.gram_orth" in text
    for big in (nnz, *slots):                                # no array of a row a nonzero
        assert not re.search(rf"f32\[{big},{s}\]", text)
        assert not re.search(rf"f32\[{s},{big}\]", text)


def test_three_tables_lower_to_fewer_gathers_than_two_did(one_chip):
    """What the TPU compiler's time for the three ASE programs grows
    with, counted where a CPU can: the gathers of the lowered sweep
    segment at the cell's size.  Before the hot table every table's
    eight gathers a step stood twice, in the loop and for the pieces
    left over, and the rows' placement once: 2 x 17 a product, four
    products in the segment (commit 92917cb).  Now a table's stand once
    (the last step is clamped), and the hot table's rows are one more."""
    n, nnz, s = _cell_sizes()

    def gathers(A):
        return len(re.findall(r'"stablehlo\.gather"\(', _sweep_segment(A, one_chip).as_text()))

    assert gathers(_prepared_shapes(n, nnz, one_chip, **CELL_LAYOUT)) == 2 * (3 * 9 + 1) < 2 * 2 * 17
    assert gathers(_prepared_shapes(n, nnz, one_chip)) == 2 * 2 * 9


# -- the polynomial kernel's TensorSketch map in the streamed trainer ---------

_PPT_SCOPES = ("ppt.hash", "ppt.dft", "ppt.product", "ppt.inverse")


def _products(text, scope):
    """(output dims but those of 1, contracted length) of each
    convolution the compiled ``text`` holds under the named ``scope``: K
    from M·K · K·N / M·N."""
    dims = {m.group(1): [int(d) for d in m.group(2).split(",")]
            for m in re.finditer(r"(%[\w.-]+) = \w+\[([\d,]+)\]", text)}
    out = []
    for m in re.finditer(r"(%[\w.-]+) = \w+\[[\d,]+\]\S* convolution\((%[\w.-]+), (%[\w.-]+)\)"
                         r'.*op_name="[^"]*/' + re.escape(scope) + "/", text):
        o, a, b = (dims[name] for name in m.groups())
        out.append((tuple(d for d in o if d != 1),
                    math.isqrt(math.prod(a) * math.prod(b) // math.prod(o))))
    return sorted(out)


@pytest.mark.parametrize("program", ["gram", "zr", "apply_delta"])
def test_streaming_krr_ppt_programs_carry_the_four_scopes(one_chip, as_tpu, program):
    """The three chunk programs of ``krr_poly2_mnist8m_resident`` (65,536
    x 784 -> 4096, q = 2, 32 panels) on the chip's route, the bf16
    half-spectrum DFT with each level's CountSketch folded into its
    forward tables: the four ``ppt.*`` scopes own the program's
    operations, the transforms, level products and inverse under the
    feature pass and the hash (the folded tables' rows) once a launch
    before it; a level's transform is two products of the panel itself
    by (d, S/2) tables, and the inverse one product of the stacked
    halves, one S x S product's work; and the temporaries leave the
    3.8 GB of resident arrays room."""
    import sys

    from libskylark_tpu.ml import PolynomialKernel
    from libskylark_tpu.ml.krr import streaming_krr_chunk_programs
    from libskylark_tpu.utils import profiling

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks"))
    import scope_reduce

    D, SZ, NB, BR, T = 784, 4096, 32, 65_536, 10
    M = PolynomialKernel(D, q=2, c=1.0, gamma=1.0 / D).create_rft(
        SZ, "regular", SketchContext(seed=20261015))
    assert M._dft_wins(jnp.dtype(BF16), BR)
    progs = dict(zip(("gram", "zr", "apply_delta"),
                     streaming_krr_chunk_programs([M], 0, NB, BR, _krr_rows, BF16)))
    lam, X, R, W = ((), F32), ((NB * BR, D), BF16), ((NB, BR, T), F32), ((SZ, T), F32)
    shapes = {"gram": (lam, X), "zr": (lam, R, W, X), "apply_delta": (R, W, X)}
    compiled = _compile(progs[program], one_chip, *shapes[program])
    text = compiled.as_text()
    for scope in _PPT_SCOPES[1:]:
        assert re.search(rf'op_name="[^"]*krr\.features/{re.escape(scope)}/', text), scope
    # the hash is the folded tables' gathered, signed rows, made with them
    assert re.search(r'op_name="jit\(\w+\)/jit\(\w+\)/ppt\.hash/', text)
    assert not re.search(r'op_name="[^"]*krr\.features/ppt\.hash/', text)
    rx = re.compile(r"^ppt\.")
    owned = {scope_reduce.scope_of(scope_reduce.owner(entry), rx)
             for entry in profiling.hlo_scopes(text).values()} - {None}
    # ppt.product owns the column-0 mask (S/2 predicates a panel); its
    # arithmetic rides in the second level's transform, as it did there
    assert owned == set(_PPT_SCOPES)
    # q = 2 levels of two (65536, 2048) spectra halves, contracting the
    # panel's d: the CountSketch rides in the tables; the inverse
    # contracts the stacked halves, S, into the panel
    assert _products(text, "ppt.dft") == [((BR, SZ // 2), D)] * 4
    assert _products(text, "ppt.inverse") == [((BR, SZ), SZ)]
    # the tables are made once a launch: one cosine and one sine in the
    # program (unbarred, the compiler fused them into each of the
    # transforms' convolutions of the loop body)
    assert len(re.findall(r" cosine\(", text)) == len(re.findall(r" sine\(", text)) == 1
    # no hashed (65536, 4096) bf16 panel a level: 2.19-2.24 GB in all,
    # where the hash, then the half spectrum, took 4.36-4.41 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 2.4e9


def test_streaming_krr_ppt_sparse_zr_program_at_the_url_cells_size(one_chip, as_tpu):
    """The ``zr`` chunk program of ``krr_poly2_url_sparse_resident``
    (45,210-row panels of 128 slots a row and 15,448 overflow rows, d =
    3,231,961 -> 4096, q = 2, 53 panels) on the chip's route: each
    level's CountSketch of the sparse panel is two one-hot products under
    ``ppt.scatter``, the rows' and the overflow rows', and one scatter of
    the overflow rows' bins at their rows, in order (no sort, no N-long
    table, no (rows, d) array: the hash comes from the columns'
    counters); the bins take the plain half-spectrum transforms; the
    temporaries leave the resident panels (2.5 GB) room."""
    from libskylark_tpu.core import sparse
    from libskylark_tpu.ml import PolynomialKernel
    from libskylark_tpu.ml.krr import streaming_krr_chunk_programs

    D, SZ, NB, BR, SLOTS, E, T = 3_231_961, 4096, 53, 45_210, 128, 15_448, 2
    M = PolynomialKernel(D, q=2, c=1.0, gamma=1 / 115.6).create_rft(
        SZ, "regular", SketchContext(seed=20261019))
    assert M._dft_wins(jnp.dtype(BF16), BR) and not M._folds()
    _, zr, _ = streaming_krr_chunk_programs([M], 0, NB, BR, sparse.panel_rows, BF16)
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    P = sparse.Panels(shaped((NB, BR, SLOTS), BF16), shaped((NB, BR, SLOTS), I32),
                      shaped((NB, E, SLOTS), BF16), shaped((NB, E, SLOTS), I32),
                      shaped((NB, E), I32), (NB * BR, D), 277_058_644)
    with jax.enable_x64(False):
        compiled = zr.lower(shaped((), F32), shaped((NB, BR, T), F32),
                            shaped((SZ, T), F32), P).compile()
    text = compiled.as_text()
    scatter_products = re.findall(
        r' convolution\(.*op_name="[^"]*krr\.features/ppt\.scatter/', text)
    assert len(scatter_products) == 4  # the rows' and the overflow rows', a level
    scatters = re.findall(r" scatter\(.*", text)
    assert len(scatters) == 2 and all("indices_are_sorted=true" in s for s in scatters)
    assert " sort(" not in text
    assert not re.search(rf"\[[\d,]*\b{D}\b", text)
    assert _products(text, "ppt.dft") == [((BR, SZ // 2), SZ)] * 4
    assert _products(text, "ppt.inverse") == [((BR, SZ), SZ)]
    assert compiled.memory_analysis().temp_size_in_bytes < 3.5e9
