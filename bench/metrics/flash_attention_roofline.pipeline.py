"""The Pallas flash-attention forward kernel's share of its roofline in the
pipeline cell, at one microbatch's attention shape (``flops.flash_roofline``).
The 1F1B backward re-runs each stage's forward, so a microbatch's layer
calls the kernel twice; each call is one."""


def read(r):
    from bench.lib import flops
    return flops.flash_roofline(r)
