"""Share of the HBM roofline that the insert frontier's round program reaches.

Bytes one dispatch needs, with no padding, for R receiver rows at width
bucket t against the flush's B inserted sources: the rows' neighbor ids,
edge lengths and the neighbors' k-th bounds (R t of each, 4 bytes), the
neighbors' rows of the (n+1, B) distance state (R t B of 4 bytes) and the
receivers' own rows, read and written (2 R B of 4 bytes). R and t are each
dispatch's ``knn:flush.frontier.part`` span attributes, B the enclosing
``knn:flush`` span's ``inserts``. Over ``_frontier_round``'s summed device
time in the traced window, against the chip's HBM peak."""
from harness import peak
from spanattrs import enclosing, window_attr_spans

PROGRAM = "jit__frontier_round"


def bytes_needed(r: int, t: int, b: int) -> int:
    return r * t * 12 + r * t * b * 4 + 2 * r * b * 4


def read(rec):
    t = rec.trace
    spans = window_attr_spans(rec)
    if t is None or not spans or not t.programs_s.get(PROGRAM):
        return None
    need = 0
    for s in spans:
        if s.name == "knn:flush.frontier.part":
            flush = enclosing(spans, s, "knn:flush")
            if flush is not None:
                need += bytes_needed(s.attrs["rows"], s.attrs["t"], flush.attrs["inserts"])
    if not need:
        return None
    return 100.0 * need / (t.programs_s[PROGRAM] * peak(rec.device_kind, "hbm_bytes_per_s"))
