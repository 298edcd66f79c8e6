"""Share of the HBM roofline that the construction sweeps reach.

Bytes one sweep needs, with no padding: for every valid bridge-neighbor
entry of the schedule, the neighbor's table row (k int32 ids, k float32
distances) and the entry itself (an int32 id and a float32 length); for
every vertex, its id, its extra-candidate row and the row it writes (k
entries of 8 bytes each). A build is two sweeps (up, down), each one
``_sweep_program`` call. Over the programs' summed device time in the trace,
against the chip's HBM peak."""
from harness import peak

PROGRAM = "jit__sweep_program"


def sweep_bytes(entries: int, n: int, k: int) -> int:
    return entries * (8 * k + 8) + n * (4 + 2 * 8 * k)


def read(rec):
    t = rec.trace
    if t is None or not t.programs_s.get(PROGRAM):
        return None
    per_build = sum(sweep_bytes(e, rec.n, rec.k) for e in rec.sweep_entries)
    builds = t.program_calls[PROGRAM] / len(rec.sweep_entries)
    return 100.0 * builds * per_build / (t.programs_s[PROGRAM]
                                         * peak(rec.device_kind, "hbm_bytes_per_s"))
