"""Set-up seconds: process start to the window's start (host clock).

Loading the network, preparing the sweeps, building the first tables,
compiling or loading every program, and the warm-up ticks."""


def read(rec):
    return rec.setup_s
