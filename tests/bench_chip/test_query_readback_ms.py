"""The ``query_readback_ms`` reader: the ``knn:query.readback`` span's
milliseconds per call in the traced window, from a trace excerpt."""
import types

import pytest

import bench_names  # noqa: F401  (puts benchmarks/chip/ on the path)
import harness
import spantrace
from spantrace import Span


def _rec(spans):
    cell = harness.Cell("c", 1, {}, {"tick": {"batch": 4096}}, [], [])
    rec = harness.Record(cell=cell, k=20, n=100)
    if spans is not None:
        rec.trace = types.SimpleNamespace(spans=spans, window_s=1.0)
    return rec


def test_query_readback_ms_reads_the_readback_span_per_call():
    # two batches in a 10 ms window: each readback follows its knn:query;
    # the third starts in the window and is clipped at its end
    excerpt = [
        Span("bench:window", 0, 10e6),
        Span("bench:query_batch", 1e6, 2e6),
        Span("knn:query", 1.0e6, 0.8e6),
        Span("knn:query.readback", 1.8e6, 1.1e6),
        Span("bench:query_batch", 4e6, 2e6),
        Span("knn:query", 4.0e6, 0.9e6),
        Span("knn:query.readback", 4.9e6, 0.9e6),
        Span("knn:query", 9.0e6, 0.8e6),
        Span("knn:query.readback", 9.8e6, 1.0e6),
    ]
    spans = spantrace.totals(excerpt, spantrace.window_of(excerpt))
    read = harness.load_metric("query_readback_ms").read
    assert read(_rec(spans)) == pytest.approx((1.1 + 0.9 + 0.2) / 3)
    # knn:query keeps its own time: the readback is not inside it
    assert harness.load_metric("query_host_ms").read(_rec(spans)) == pytest.approx(2.5 / 3)


def test_query_readback_ms_reads_nothing_without_the_span():
    """A program without the span (one whose caller reads the answer
    back) and an untraced run give no reading, and no error."""
    read = harness.load_metric("query_readback_ms").read
    assert read(_rec({"knn:query": [0.003, 4]})) is None
    assert read(_rec({})) is None
    assert read(_rec(None)) is None
