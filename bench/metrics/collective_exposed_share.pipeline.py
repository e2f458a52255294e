"""Share of the window in which a chip runs a collective and nothing else:
per chip, the union of its collective ops' intervals less the union of its
other ops', over the window, averaged over the chips (device trace).  The
control-flow ops that wrap the step's scan and its per-slot switch
(``while``, ``conditional``) count as neither (``trace.alone_seconds``).

In a traced 1F1B step on a v5e 2x2 the collectives are the two hand-offs
of every slot, ``collective-permute-start`` and ``collective-permute-done``
(the wire code forward, its cotangent back; the start holds the wait for
the neighbour), and the ``all-reduce`` ops (``%psum``, ``%all-reduce``) of
the loss and the shared gradients at the step's end.  A chip that waits
there for a neighbour's code is the bubble and the stages' imbalance.

A collective is an op whose HLO text names one of ``COLLECTIVES`` as its
opcode, with or without the async ``-start`` / ``-done`` split."""

import re

COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "reduce-scatter", "all-to-all", "collective-broadcast")
OPCODE = re.compile(r"\b(?:%s)(?:-start|-done)?\(" % "|".join(COLLECTIVES))


def read(r):
    from bench.lib import trace as tr
    window = (r.hi - r.lo) / 1e9
    if window <= 0 or not r.trace["devices"]:
        return None
    alone = tr.alone_seconds(r.trace, lambda n: bool(OPCODE.search(n)),
                             r.lo, r.hi)
    return 100.0 * alone / window
