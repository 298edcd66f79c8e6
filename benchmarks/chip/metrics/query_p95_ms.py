"""95th percentile over every query of the window, from its batch's issue
to its answer read back on the host (nearest rank, host clock)."""
from harness import percentile


def read(rec):
    p = percentile(rec.batch_lat_s, rec.batch_size, 95)
    return None if p is None else p * 1e3
