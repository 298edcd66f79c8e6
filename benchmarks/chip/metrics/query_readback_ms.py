"""Host milliseconds a query batch waits for its answer: the
``knn:query.readback`` span's seconds per call over the traced window (the
packed answer's one device->host transfer, after ``knn:query`` closes)."""
from spantrace import window_spans


def read(rec):
    r = (window_spans(rec) or {}).get("knn:query.readback")
    return 1e3 * r[0] / r[1] if r and r[1] else None
