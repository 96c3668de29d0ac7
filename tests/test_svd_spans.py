"""The randomized SVD's stage spans, read back from a profiler trace on
the CPU: ``svd.sketch``, ``svd.power``, ``svd.project``, ``svd.small``,
``svd.rotate`` open once a call and in that order, inside the entry span
``randomized_svd``; ``guard.certify`` follows them; a ladder's second
attempt opens them again; ``info["attempts"]`` counts the factorizations.

A file of its own: a process has one profiler session at a time, and the
suite gives a file to one worker (``tests/test_stage_spans.py`` holds the
solvers' and the trainer's).
"""

import jax.numpy as jnp
import numpy as np
import pytest
from test_stage_spans import under_profiler  # the suite's one trace-and-read helper

from libskylark_tpu import SketchContext, guard
from libskylark_tpu.linalg import SVDParams, approximate_svd

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
