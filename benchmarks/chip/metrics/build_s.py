"""Seconds per index build: the window over the builds it completed."""


def read(rec):
    return rec.window_s / len(rec.builds_s) if rec.builds_s else None
