"""The randomized SVD's stage spans, read back from a profiler trace on
the CPU: ``svd.sketch``, ``svd.power``, ``svd.project``, ``svd.small``,
``svd.rotate`` open once a call and in that order, inside the entry span
``randomized_svd``; ``guard.certify`` follows them; a ladder's second
attempt opens them again; ``info["attempts"]`` counts the factorizations.
With ``SKYLARK_TELEMETRY`` on, the ledger says that the sweep segment is
built by the first call at a shape and by no later one.

A file of its own: a process has one profiler session at a time, and the
suite gives a file to one worker (``tests/test_stage_spans.py`` holds the
solvers' and the trainer's).
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest
from test_stage_spans import under_profiler  # the suite's one trace-and-read helper

from libskylark_tpu import SketchContext, guard, telemetry
from libskylark_tpu.linalg import SVDParams, approximate_svd, svd

pytestmark = pytest.mark.telemetry

STAGES = ["svd.sketch", "svd.power", "svd.project", "svd.small", "svd.rotate"]


def _operand():
    rng = np.random.default_rng(9)
    A = rng.standard_normal((1024, 5)) @ rng.standard_normal((5, 32))
    return jnp.asarray(A + 0.01 * rng.standard_normal(A.shape), jnp.float32)


def _factor(A, **kw):
    return approximate_svd(A, 5, SketchContext(seed=13),
                           SVDParams(num_iterations=2), return_info=True, **kw)


def spans_under_profiler(call, trace_dir):
    """``call()`` under a profiler session and the ``(name, start, end)``
    of the ``skylark:`` spans of its trace, by start."""
    out, spans = under_profiler(call, trace_dir)
    return out, sorted(spans, key=lambda span: span[1])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    A = _operand()
    plain = _factor(A)
    under, spans = spans_under_profiler(lambda: _factor(A), tmp_path_factory.mktemp("svd"))
    return plain, under, spans


def test_the_five_stage_spans_open_once_a_call_in_order(traced):
    _, _, spans = traced
    assert [name for name, _, _ in spans] == ["randomized_svd", *STAGES, "guard.certify"]
    ends = [e for _, _, e in spans[1:]]
    starts = [s for _, s, _ in spans[1:]]
    assert all(e <= s for e, s in zip(ends, starts[1:]))  # one after another


def test_the_stage_spans_lie_inside_the_entry_span(traced):
    _, _, spans = traced
    (_, lo, hi), stages = spans[0], spans[1:]
    assert all(lo <= s and e <= hi for _, s, e in stages)


def test_the_answer_under_a_profiler_session_is_bit_identical(traced):
    ((U0, s0, V0), info0), ((U1, s1, V1), info1), _ = traced
    for a, b in ((U0, U1), (s0, s1), (V0, V1)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert info0 == info1 and info0["attempts"] == 1
    assert [a["verdict"] for a in info0["recovery"]["attempts"]] == ["OK"]


def test_a_second_attempt_opens_the_stage_spans_again(tmp_path_factory, monkeypatch):
    """A certificate that fails once sends the call up the ladder: the
    five stages and the certificate run again with a fresh sketch."""
    real, calls = guard.certify_svd, []

    def failing_once(A, U, s, V, **kw):
        calls.append(1)
        cert = real(A, U, s, V, **kw)
        if len(calls) == 1:
            cert.verdict, cert.detail = guard.RESKETCH, "planted"
        return cert

    monkeypatch.setattr(guard, "certify_svd", failing_once)
    (_, info), spans = spans_under_profiler(lambda: _factor(_operand()),
                                      tmp_path_factory.mktemp("svd_ladder"))
    assert [name for name, _, _ in spans] == [
        "randomized_svd", *STAGES, "guard.certify", *STAGES, "guard.certify"]
    assert info["attempts"] == 2 and info["recovery"]["recovered"] is True
    assert [a["action"] for a in info["recovery"]["attempts"]] == ["initial", "resketch"]


def test_with_the_guard_off_one_factorization_and_no_certificate(tmp_path_factory,
                                                                monkeypatch):
    monkeypatch.setenv("SKYLARK_GUARD", "0")
    (_, info), spans = spans_under_profiler(lambda: _factor(_operand()),
                                      tmp_path_factory.mktemp("svd_unguarded"))
    assert [name for name, _, _ in spans] == ["randomized_svd", *STAGES]
    assert info["attempts"] == 1 and info["recovery"]["guarded"] is False


def test_with_telemetry_on_only_the_cold_call_builds_under_svd_power(tmp_path, monkeypatch):
    """The sweep segment is the module-level ``svd._chunk``: traced and
    lowered by the first call at a shape, dispatched from ``jax.jit``'s
    cache by every later one.  ``snapshot()`` sums it by span name, so the
    segment's hit share is 1 - lowerings / calls of ``svd.power``."""
    A = _operand()
    off = _factor(A)
    svd._chunk.clear_cache()  # the first call below is the cold one
    monkeypatch.setenv("SKYLARK_TELEMETRY", "1")
    telemetry.configure(str(tmp_path))
    telemetry.reset()
    try:
        on = [_factor(A), _factor(A), _factor(A)]
        telemetry.flush()
        with open(telemetry.ledger_path()) as fh:
            events = [json.loads(line) for line in fh]
        snap = telemetry.snapshot()
    finally:
        telemetry.close()
        telemetry.configure(None)
        telemetry.reset()
    for (U, s, V), info in on:
        assert info == off[1]
        for a, b in zip((U, s, V), off[0], strict=True):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    ends = {stage: [e["attrs"] for e in events
                    if e["kind"] == "span_end" and e["name"] == stage]
            for stage in ["randomized_svd", *STAGES]}
    assert all(len(v) == 3 for v in ends.values())  # once a call, every call
    cold, *warm = ends["svd.power"]
    assert cold["lowerings"] == 1 and cold["lower_s"] > 0
    assert cold["traces"] >= 1 and cold["trace_s"] > 0
    for attrs in warm:
        assert not {"lowerings", "traces", "compiles"} & set(attrs)
    # the entry span holds its stages' builds; a warm call holds none at all
    assert ends["randomized_svd"][0]["lowerings"] >= 1
    for attrs in ends["randomized_svd"][1:]:
        assert "lowerings" not in attrs and "traces" not in attrs
    power = snap["spans"]["svd.power"]
    assert power["calls"] == 3 and power["lowerings"] == 1
    assert power["lower_s"] == pytest.approx(cold["lower_s"], abs=1e-5)
