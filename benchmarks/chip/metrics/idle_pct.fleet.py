"""Device idle share of the traced window, in percent: 1 minus the union
of the device's op intervals over the window's length."""


def read(rec):
    return None if rec.trace is None else 100.0 * rec.trace.idle_share
