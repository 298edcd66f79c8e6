"""Share of the HBM roofline that the ``serve_gather`` program reaches.

Bytes the gather needs per batch of B queries at index width k: the B table
rows read (k int32 ids and k float32 distances each), the (B, k) answer
written (the same), and the B query ids and B per-query ks read. Over the
program's summed device time in the trace, against the chip's HBM peak.
The gather does no arithmetic to speak of, so bytes bound it."""
from harness import peak

PROGRAM = "jit_serve_gather"


def bytes_needed(b: int, k: int) -> int:
    return 2 * b * k * 8 + 2 * b * 4


def read(rec):
    t = rec.trace
    if t is None or not t.programs_s.get(PROGRAM):
        return None
    b = int(rec.cell.traffic["tick"]["batch"])
    need = t.program_calls[PROGRAM] * bytes_needed(b, rec.k)
    return 100.0 * need / (t.programs_s[PROGRAM] * peak(rec.device_kind, "hbm_bytes_per_s"))
