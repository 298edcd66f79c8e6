"""The program's spans in a traced window (``spantrace``) and the readers
of the per-layer metrics that read them, on hand-made spans and records."""
import types

import pytest

import bench_names  # noqa: F401  (puts benchmarks/chip on the path)
import harness
import spantrace
from spantrace import Span


def _s(name, start, dur):
    return Span(name, float(start), float(dur))


def test_a_gap_goes_to_the_innermost_span():
    spans = [
        _s("bench:window", 0, 100),
        _s("bench:flush", 10, 80),
        _s("knn:flush", 12, 76),
        _s("knn:flush.repair", 40, 40),
        _s("knn:flush.repair.round", 42, 20),
        _s("knn:flush.readback", 55, 5),
    ]
    # device busy 0..44 and 50..70: gaps 44..50 (in the round), 70..100
    # (middle 85: the round and the repair have ended, the flush has not)
    gaps = spantrace.idle_gaps(spans, [(0, 44), (50, 70)], (0, 100))
    assert gaps == pytest.approx({"knn:flush.repair.round": 6e-9, "knn:flush": 30e-9})


def test_a_gap_after_a_child_ends_goes_to_its_parent():
    spans = [_s("bench:window", 0, 100), _s("bench:flush", 10, 80),
             _s("knn:flush", 10, 80), _s("knn:flush.frontier", 10, 30)]
    starts = [s.start_ns for s in spans]
    assert spantrace.innermost(spans, starts, 20) == "knn:flush.frontier"
    assert spantrace.innermost(spans, starts, 60) == "knn:flush"
    assert spantrace.innermost(spans, starts, 95) is None
    # gaps 15..30 (in the frontier), 50..80 (after it), 90..100 (in no span)
    gaps = spantrace.idle_gaps(spans, [(0, 15), (30, 50), (80, 90)], (0, 100))
    assert gaps == pytest.approx(
        {"knn:flush.frontier": 15e-9, "knn:flush": 30e-9, "other": 10e-9})


def test_totals_sum_and_count_per_name_clipped_to_the_window():
    spans = [
        _s("bench:window", 100, 1000),
        _s("knn:flush", 50, 100),       # 100..150 inside
        _s("knn:flush", 500, 200),
        _s("knn:flush", 1050, 100),     # 1050..1100 inside
        _s("knn:flush", 1200, 10),      # outside: not counted
        _s("knn:flush.readback", 510, 5),
        _s("bench:flush", 500, 300),    # the harness's spans are not totalled
    ]
    tot = spantrace.totals(spans, spantrace.window_of(spans))
    assert tot == {"knn:flush": [pytest.approx(300e-9), 3],
                   "knn:flush.readback": [pytest.approx(5e-9), 1]}


def test_span_names_drop_the_annotation_metadata():
    assert spantrace.span_name("knn:flush#epoch=3,staged=12#") == "knn:flush"
    assert spantrace.span_name("knn:query") == "knn:query"


def _rec(spans, traced=True):
    cell = harness.Cell("c", 1, {}, {"tick": {"batch": 4096}}, [], [])
    rec = harness.Record(cell=cell, k=20, n=100)
    if traced:
        rec.trace = types.SimpleNamespace(spans=spans, window_s=1.0)
    return rec


def test_query_host_ms_reads_the_query_span_per_call():
    read = harness.load_metric("query_host_ms").read
    assert read(_rec({"knn:query": [0.003, 4], "knn:flush": [4.0, 2]})) == pytest.approx(0.75)


def test_query_host_ms_reads_nothing_from_a_program_without_spans():
    """A program that has no ``knn:`` spans gives no reading, and no error;
    neither does an untraced run."""
    read = harness.load_metric("query_host_ms").read
    assert read(_rec({})) is None
    assert read(_rec({"knn:query": [0.003, 4]}, traced=False)) is None


def test_window_spans_reads_the_trace_file_of_the_run(tmp_path, monkeypatch):
    """Without spans on the Summary, the reader parses the run's trace file,
    and only when its window is the one the Summary was reduced over."""
    import jax

    from repro.core.spans import span

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench:window"):
        for batch in range(2):
            with span("query", batch=batch, epoch=0, b=8):
                with span("query.gather"):
                    pass
    jax.profiler.stop_trace()
    monkeypatch.setattr(spantrace, "TRACE_DIR", tmp_path)
    spans = spantrace.events(next(tmp_path.rglob("*.xplane.pb")))
    w0, w1 = spantrace.window_of(spans)
    rec = _rec(None)
    rec.trace = types.SimpleNamespace(window_s=(w1 - w0) * 1e-9)
    got = spantrace.window_spans(rec)
    assert {k: v[1] for k, v in got.items()} == {"knn:query": 2, "knn:query.gather": 2}
    assert harness.load_metric("query_host_ms").read(rec) == pytest.approx(
        1e3 * got["knn:query"][0] / 2)
    rec.trace = types.SimpleNamespace(window_s=(w1 - w0) * 1e-9 + 1e-3)
    assert spantrace.window_spans(rec) is None
