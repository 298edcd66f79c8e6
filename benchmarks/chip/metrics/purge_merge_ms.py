"""Device milliseconds of a flush's fused purge and merge: the
``rows_purge_merge`` program's summed device time in the traced window over
the window's flushes."""

PROGRAM = "jit_rows_purge_merge"


def read(rec):
    t = rec.trace
    if t is None or not rec.flushes or not t.programs_s.get(PROGRAM):
        return None
    return 1e3 * t.programs_s[PROGRAM] / len(rec.flushes)
