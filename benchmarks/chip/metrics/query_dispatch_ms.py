"""Host milliseconds until ``query_batch`` returns (before the readback),
the mean over the window's batches, from the harness's own spans."""


def read(rec):
    return 1e3 * sum(rec.dispatch_s) / len(rec.dispatch_s) if rec.dispatch_s else None
