"""Rounds a flush runs: its insert frontier's rounds plus its repair rounds
(the counts ``flush_updates`` returns), averaged over the traced window's
flushes."""


def read(rec):
    if rec.trace is None or not rec.flushes:
        return None
    return sum(f["frontier_rounds"] + f["repair_rounds"] for f in rec.flushes) / len(rec.flushes)
