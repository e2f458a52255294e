"""Model FLOP utilization of the swarm's whole epochs (``flops.mfu``): the
tokens of the window's epochs over those epochs' time."""


def read(r):
    from bench.lib import flops
    return flops.mfu(r)
