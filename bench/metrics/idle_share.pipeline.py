"""Share of the window in which no operation ran on a chip, averaged over
the four chips (device trace, ``trace.idle_share``).  The step's scan
(``while``) and its per-slot switch (``conditional``) are not operations
here, so the gaps between a slot's ops count as idle; a chip that waits
for its neighbour's code inside ``collective-permute-done`` is busy, and
shows in ``collective_exposed_share.pipeline``."""


def read(r):
    from bench.lib import trace as tr
    return tr.idle_share(r.trace, r.lo, r.hi)
