"""Device time of the stage programs per tick: the compiled
``stage_forward``, ``stage_backward`` and ``last_stage_loss_and_grads``
modules in the trace, over the ticks trained in the window.  The optimizer's
per-leaf updates and the codecs are not in it."""

PROGRAMS = ("stage_forward", "stage_backward", "last_stage_loss_and_grads")


def _match(name):
    return any(p in name for p in PROGRAMS)


def read(r):
    from bench.lib import trace as tr
    rows = tr.events(r.trace, "modules", _match, r.lo, r.hi)
    ticks = r.ctx["ticks"]
    if not rows or not ticks:
        return None
    return sum(row[2] for _, row in rows) / 1e6 / len(r.trace["devices"]) \
        / ticks
