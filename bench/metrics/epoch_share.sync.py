"""Share of the window's epochs spent in sharing, sync and the reduce audit,
from the benchmark's spans around those phases (host clock)."""

SYNC = ("sharing", "sync", "reduce_audit")


def read(r):
    ctx = r.ctx
    lo = ctx["window_start_ns"]
    epochs = sum(e - s for n, s, e in ctx["spans"] if n == "epoch" and s >= lo)
    part = sum(e - s for n, s, e in ctx["spans"] if n in SYNC and s >= lo)
    if not epochs:
        return None
    return 100.0 * part / epochs
