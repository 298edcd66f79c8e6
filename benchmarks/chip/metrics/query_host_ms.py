"""Host milliseconds of a query batch inside the program: the ``knn:query``
span's seconds per call over the traced window (the per-query k upload,
the gather's uploads and dispatch; no readback)."""
from spantrace import window_spans


def read(rec):
    q = (window_spans(rec) or {}).get("knn:query")
    return 1e3 * q[0] / q[1] if q and q[1] else None
