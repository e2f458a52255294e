"""The Pallas int8 share-codec kernel's (``quant_stream`` quantize) share of
its roofline: the least time of the bytes it must move for the vector it
was given, over the time each call took, summed over calls.

On the chip the kernel is one ``tpu_custom_call`` op whose output is the
tuple (s8[rows, 128] codes, f32 scales); the vector's length is read from
that output's shape in the op's HLO text."""

import re

CODES = re.compile(r"= \(s8\[(\d+),(\d+)\]")


def read(r):
    from bench.lib import flops, trace as tr
    rows = tr.events(r.trace, "ops",
                     lambda n: "tpu_custom_call" in n and CODES.search(n),
                     r.lo, r.hi)
    least = took = 0.0
    for _, row in rows:
        rows_, width = CODES.search(row[0]).groups()
        n = int(rows_) * int(width)
        least += flops.least_seconds(*flops.quantize_int8(n), r.peaks)[0]
        took += row[2] / 1e9
    if not took:
        return None
    return 100.0 * least / took
