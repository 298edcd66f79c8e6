"""Host milliseconds a flush waits on the device: the ``knn:flush.readback``
spans' seconds per ``knn:flush`` span of the traced window."""
from spantrace import window_spans


def read(rec):
    s = window_spans(rec) or {}
    f, r = s.get("knn:flush"), s.get("knn:flush.readback")
    return 1e3 * r[0] / f[1] if f and r else None
