"""Share of the window's epochs spent in the training phase, from the
benchmark's span around it (host clock).  The rest is the epoch-start
snapshot, the other phases and the epoch's book-keeping."""


def read(r):
    ctx = r.ctx
    lo = ctx["window_start_ns"]
    epochs = sum(e - s for n, s, e in ctx["spans"] if n == "epoch" and s >= lo)
    part = sum(e - s for n, s, e in ctx["spans"]
               if n == "training" and s >= lo)
    if not epochs:
        return None
    return 100.0 * part / epochs
