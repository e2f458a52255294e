"""Share of the window in which no operation ran on the chip (device
trace, ``trace.idle_share``)."""


def read(r):
    from bench.lib import trace as tr
    return tr.idle_share(r.trace, r.lo, r.hi)
