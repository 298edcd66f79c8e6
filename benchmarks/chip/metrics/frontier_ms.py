"""Host milliseconds of a flush's insert frontier: the ``knn:flush.frontier``
spans' seconds per ``knn:flush`` span of the traced window (its rounds,
their readbacks, and the candidates' extraction)."""
from spantrace import window_spans


def read(rec):
    s = window_spans(rec) or {}
    f, p = s.get("knn:flush"), s.get("knn:flush.frontier")
    return 1e3 * p[0] / f[1] if f and p else None
