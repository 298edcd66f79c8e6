"""Host milliseconds of a flush's repair rounds: the ``knn:flush.repair``
spans' seconds per ``knn:flush`` span of the traced window."""
from spantrace import window_spans


def read(rec):
    s = window_spans(rec) or {}
    f, p = s.get("knn:flush"), s.get("knn:flush.repair")
    return 1e3 * p[0] / f[1] if f and p else None
