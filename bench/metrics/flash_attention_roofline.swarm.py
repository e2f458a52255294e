"""The Pallas flash-attention forward kernel's share of its roofline in the
swarm cells, at the cell's attention shape, on the op inside the stage
programs (``flops.flash_roofline``)."""


def read(r):
    from bench.lib import flops
    return flops.flash_roofline(r)
