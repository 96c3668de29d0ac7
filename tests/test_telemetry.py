"""Telemetry-layer tests: registry, spans, JSONL ledger schema, and the
acceptance run of docs/observability.md — a guarded streaming least-
squares pass with an injected sketch fault, checked against its ledger.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring

from libskylark_tpu import plans, telemetry
from libskylark_tpu.core.context import SketchContext
from libskylark_tpu.linalg import streaming_least_squares
from libskylark_tpu.resilient import FaultPlan
from libskylark_tpu.streaming import StreamParams

pytestmark = pytest.mark.telemetry

N, D, BATCH = 96, 6, 12  # 8 batches per pass


def _make_problem(rank_deficient=False):
    rng = np.random.default_rng(7)
    A = rng.standard_normal((N, D))
    if rank_deficient:
        # Duplicate column: S·A is numerically singular for any linear
        # sketch, so certify_sketch must return a RESKETCH verdict.
        A[:, -1] = A[:, 0]
    b = rng.standard_normal(N)
    return A, b


def _batches(A, b):
    def factory(start):
        def gen():
            for i in range(start, N // BATCH):
                sl = slice(i * BATCH, (i + 1) * BATCH)
                yield A[sl], b[sl]

        return gen()

    return factory


@pytest.fixture
def ledger_dir(tmp_path, monkeypatch):
    """Telemetry ON with a fresh ledger in tmp_path; fully unwound after."""
    monkeypatch.setenv("SKYLARK_TELEMETRY", "1")
    telemetry.configure(str(tmp_path))
    telemetry.reset()
    plans.reset()
    yield tmp_path
    telemetry.close()
    telemetry.configure(None)
    telemetry.reset()


def _read_ledger():
    telemetry.flush()
    path = telemetry.ledger_path()
    assert path is not None, "no ledger file was opened"
    with open(path, encoding="utf-8") as fh:
        return [json.loads(line) for line in fh]


class TestRegistry:
    def test_counters_gauges_histograms(self, monkeypatch):
        monkeypatch.setenv("SKYLARK_TELEMETRY", "1")
        telemetry.reset()
        try:
            telemetry.inc("a.calls")
            telemetry.inc("a.calls", 2)
            telemetry.set_gauge("g", 1.5)
            telemetry.observe("h", 2.0)
            telemetry.observe("h", 4.0)
            snap = telemetry.snapshot()
            assert snap["counters"]["a.calls"] == 3
            assert snap["gauges"]["g"] == 1.5
            h = snap["histograms"]["h"]
            assert (h["count"], h["sum"], h["min"], h["max"]) == (2, 6.0, 2.0, 4.0)
        finally:
            telemetry.reset()

    def test_disabled_path_is_inert(self, monkeypatch):
        monkeypatch.setenv("SKYLARK_TELEMETRY", "1")
        telemetry.reset()
        try:
            telemetry.inc("a.calls")
            monkeypatch.setenv("SKYLARK_TELEMETRY", "0")
            telemetry.inc("a.calls")
            telemetry.set_gauge("g", 9)
            telemetry.observe("h", 9)
            # a disabled span is the bare profiler annotation: no event,
            # no counter, no sync of an assigned result, no listener
            before = len(monitoring.get_event_duration_listeners())
            with telemetry.span("x", k=1) as sp:
                sp.result = jnp.ones(3)
            assert type(sp) is jax.profiler.TraceAnnotation
            assert len(monitoring.get_event_duration_listeners()) == before
            assert telemetry.event("k", "n", {"a": 1}) is None
            assert telemetry.emit("k", "n", a=1) is None
            assert telemetry.run_summary("n", {"a": 1}) is None
            snap = telemetry.snapshot()
            assert snap["counters"]["a.calls"] == 1
            assert "g" not in snap["gauges"] and "h" not in snap["histograms"]
            assert "span.x.calls" not in snap["counters"] and "spans" not in snap
        finally:
            telemetry.reset()

    def test_report_reuses_timer_table(self, monkeypatch):
        monkeypatch.setenv("SKYLARK_TELEMETRY", "1")
        telemetry.reset()
        try:
            telemetry.inc("x.count", 5)
            telemetry.set_gauge("rate", 2.5)
            rep = telemetry.report()
            assert "x.count" in rep and "gauge.rate" in rep
            # Single-process distributed path reduces over 1 rank.
            rep_d = telemetry.report(distributed=True)
            assert "over 1 process" in rep_d
        finally:
            telemetry.reset()


class TestLedger:
    def test_span_nesting_and_schema(self, ledger_dir):
        with telemetry.span("outer", stage="t"):
            with telemetry.span("inner") as si:
                si.attrs["late"] = 1
        events = _read_ledger()
        for ev in events:
            assert set(ev) == {"ts", "seq", "pid", "kind", "name", "attrs"}
            assert ev["pid"] == os.getpid()
        seqs = [ev["seq"] for ev in events]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
        assert [(ev["kind"], ev["name"]) for ev in events] == [
            ("span_start", "outer"),
            ("span_start", "inner"),
            ("span_end", "inner"),
            ("span_end", "outer"),
        ]
        outer_start, inner_start, inner_end, _ = events
        assert inner_start["attrs"]["parent"] == outer_start["seq"]
        assert inner_start["attrs"]["depth"] == 1
        assert inner_end["attrs"]["late"] == 1  # amended inside the region
        assert inner_end["attrs"]["span"] == inner_start["seq"]
        assert inner_end["attrs"]["seconds"] >= 0
        snap = telemetry.snapshot()
        assert snap["counters"]["span.outer.calls"] == 1
        assert snap["counters"]["span.inner.calls"] == 1

    def test_numpy_attrs_coerce_to_json(self, ledger_dir):
        telemetry.emit(
            "probe", "coerce",
            i=np.int64(3), f=np.float32(1.5), a=np.arange(2),
        )
        (ev,) = _read_ledger()
        assert ev["attrs"] == {"i": 3, "f": 1.5, "a": [0, 1]}

    def test_no_directory_means_no_file(self, monkeypatch):
        monkeypatch.setenv("SKYLARK_TELEMETRY", "1")
        monkeypatch.delenv("SKYLARK_TELEMETRY_DIR", raising=False)
        telemetry.configure(None)
        telemetry.reset()
        try:
            seq1 = telemetry.emit("probe", "nofile", k=1)
            seq2 = telemetry.emit("probe", "nofile", k=2)
            # Events still sequence (registry/summary keep working) but
            # nothing opens on disk.
            assert seq1 is not None and seq2 == seq1 + 1
            assert telemetry.ledger_path() is None
        finally:
            telemetry.reset()


@pytest.mark.streaming
@pytest.mark.guard
class TestAcceptance:
    """The ISSUE acceptance run: SKYLARK_TELEMETRY=1, streaming guarded
    least squares, one injected ``bad_sketch_at`` fault, rank-deficient A
    (so certification fails with a RESKETCH verdict)."""

    def _run(self):
        A, b = _make_problem(rank_deficient=True)
        return streaming_least_squares(
            _batches(A, b), N, D, SketchContext(seed=3),
            stream_params=StreamParams(),
            fault_plan=FaultPlan(bad_sketch_at=1),
        )

    def test_ledger_records_the_run(self, ledger_dir, monkeypatch):
        monkeypatch.setenv("SKYLARK_GUARD", "1")
        x, info = self._run()
        events = _read_ledger()
        kinds = {(e["kind"], e["name"]) for e in events}

        # Chunk spans from the streaming engine.
        assert ("span_start", "stream.chunk") in kinds
        assert ("span_end", "stream.chunk") in kinds
        chunk_ends = [
            e for e in events
            if e["kind"] == "span_end" and e["name"] == "stream.chunk"
        ]
        assert all("rows" in e["attrs"] for e in chunk_ends)

        # The Inf-poisoned batch tripped the sentinel and was replayed.
        replays = [
            e for e in events if e["kind"] == "guard" and e["name"] == "replay"
        ]
        assert len(replays) == 1

        # Certification of the rank-deficient stream: RESKETCH verdict on
        # the initial rung, then the SVD small-solve fallback.
        initial = [
            e for e in events if e["kind"] == "guard" and e["name"] == "initial"
        ]
        assert initial and initial[-1]["attrs"]["verdict"] == "RESKETCH"
        assert any(
            e["kind"] == "guard" and e["name"] == "fallback" for e in events
        )

        # Terminal run_summary: last word of the ledger, carrying the
        # run's info dict and the registry + plan-cache snapshot.
        summaries = [e for e in events if e["kind"] == "run_summary"]
        assert len(summaries) == 1 and summaries[0]["name"] == "streaming_lsq"
        assert summaries[0]["seq"] == max(e["seq"] for e in events)
        payload = summaries[0]["attrs"]
        assert set(payload["info"]) == set(info)
        assert payload["info"]["recovery"] == info["recovery"]
        assert payload["info"]["rows"] == N
        # Counters in the summary snapshot match plans.stats(): nothing
        # touched the plan cache after the terminal event.
        assert payload["snapshot"]["plans"] == plans.stats()
        # The replay registered in the counter groups too.
        assert payload["snapshot"]["guard"].get("replay") == 1
        assert payload["snapshot"]["counters"]["stream.replays"] == 1

    def test_disabled_run_is_bit_identical(self, tmp_path, monkeypatch):
        monkeypatch.setenv("SKYLARK_GUARD", "1")
        monkeypatch.delenv("SKYLARK_TELEMETRY", raising=False)
        telemetry.close()
        x_off, info_off = self._run()
        monkeypatch.setenv("SKYLARK_TELEMETRY", "1")
        telemetry.configure(str(tmp_path))
        telemetry.reset()
        plans.reset()
        try:
            x_on, info_on = self._run()
        finally:
            telemetry.close()
            telemetry.configure(None)
            telemetry.reset()
        np.testing.assert_array_equal(np.asarray(x_off), np.asarray(x_on))
        assert info_off["recovery"] == info_on["recovery"]
        assert info_off["rows"] == info_on["rows"]
        assert info_off["batches"] == info_on["batches"]
