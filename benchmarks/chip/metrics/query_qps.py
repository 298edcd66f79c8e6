"""Queries answered per second: every query of the window over the window."""


def read(rec):
    return rec.queries / rec.window_s if rec.queries else None
