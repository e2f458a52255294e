"""Model FLOP utilization of the pipeline's whole step over the window
(``flops.mfu``): neither the 1F1B backward's re-run of each stage's
forward nor the bubble counts as model FLOPs."""


def read(r):
    from bench.lib import flops
    return flops.mfu(r)
