"""The Pallas flash-attention forward kernel's share of its roofline in the
swarm cells: the least time of its causal operations and bytes at the
cell's attention shape (``bench/lib/flops.py``), over the time each call
took in the trace, summed over calls.

On the chip each call is one op inside the stage programs: the Pallas
custom call, which the trace names ``closed_call`` (the ``custom_vjp``
body it lowers from), with its head-major output
bf16[batch, heads, seq, head_dim]; it is found by those two marks."""


def read(r):
    from bench.lib import flops, trace as tr
    a = r.ctx["attention"]
    out = "= bf16[{},{},{},{}]".format(a["batch"], a["heads"], a["seq"],
                                       a["head_dim"])

    def match(name):
        return ("closed_call" in name or "tpu_custom_call" in name) \
            and out in name

    rows = tr.events(r.trace, "ops", match, r.lo, r.hi)
    if not rows:
        return None
    ops, nbytes = flops.flash_forward(a["batch"], a["seq"], a["heads"],
                                      a["kv_heads"], a["head_dim"])
    least, _ = flops.least_seconds(ops, nbytes, r.peaks)
    took = sum(row[2] for _, row in rows) / 1e9
    return 100.0 * least * len(rows) / took
