"""Share of the window in which no operation ran on the chip: one minus the
union of the device's op intervals over the window (device trace)."""


def read(r):
    from bench.lib import trace as tr
    window = (r.hi - r.lo) / 1e9
    if window <= 0 or not r.trace["devices"]:
        return None
    return 100.0 * (1.0 - tr.busy_seconds(r.trace, r.lo, r.hi) / window)
