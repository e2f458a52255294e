"""Model FLOP utilization of the swarm's whole epoch: the model FLOPs of
the tokens trained in the window (``bench/lib/flops.py``'s convention, no
recomputed work) over the epochs' time, the chips and the bf16 peak."""


def read(r):
    ctx = r.ctx
    if not ctx["tokens"] or not ctx["epoch_seconds"]:
        return None
    done = ctx["tokens"] * ctx["flops_per_token"]
    return 100.0 * done / (ctx["epoch_seconds"] * r.chips
                           * r.peaks["bf16_flops"])
