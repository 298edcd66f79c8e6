"""Blocking readbacks a flush makes: ``knn:flush.readback`` spans per
``knn:flush`` span of the traced window."""
from spantrace import window_spans


def read(rec):
    s = window_spans(rec) or {}
    f, r = s.get("knn:flush"), s.get("knn:flush.readback")
    return r[1] / f[1] if f and r else None
