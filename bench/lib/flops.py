"""Operations and bytes the algorithms need, from shapes alone.

Conventions, fixed here so that every PR counts alike:

* Model FLOPs per trained token are 6 x the matrix-product parameters one
  token passes through (2 forward, 4 backward), plus the attention products:
  causal QK^T and PV over a sequence of S cost 2 * S * H * D per token and
  layer forward (half of the full square), 6 * S * H * D with the backward.
  Embedding lookups, norms and elementwise work are not counted.  Work that
  is done again (a backward that recomputes its forward, a validator's
  replay) is not counted either.
* A kernel's least time is the larger of its operations over the peak rate
  and its bytes over the peak bandwidth; its share of the roofline is that
  least time over the time it took.
"""
from __future__ import annotations


def train_flops_per_token(m: dict, family, n_layers: int, seq_len: int,
                          bottleneck_dim: int, n_boundaries: int) -> float:
    """Model FLOPs per trained token: the family's layers
    (``block_flops_per_token`` of its module), the unembedding over the
    real vocabulary, and one bottleneck encode and decode per stage
    boundary."""
    d = m["hidden_size"]
    shared = m["vocab_size"] * d + n_boundaries * 2 * d * bottleneck_dim
    return float(family.block_flops_per_token(m, n_layers, seq_len)
                 + 6 * shared)


def flash_forward(batch: int, seq: int, heads: int, kv_heads: int,
                  head_dim: int, itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of one causal attention forward: QK^T and PV
    over the causal triangle (S (S + 1) / 2 pairs per head), reading q, k,
    v once and writing the output once."""
    pairs = seq * (seq + 1) / 2
    ops = 4.0 * batch * heads * head_dim * pairs
    nbytes = itemsize * batch * seq * head_dim * (2 * heads + 2 * kv_heads)
    return ops, float(nbytes)


def quantize_int8(n: int, block: int = 256) -> tuple[float, float]:
    """(operations, bytes) of blockwise int8 quantization of n float32
    values: read 4 bytes, write 1 byte each, and one float32 scale per
    block; about three operations per value (abs-max, divide, round)."""
    return 3.0 * n, float(4 * n + n + 4 * (n // block))


def least_seconds(ops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    t_c = ops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


# ---------------------------------------------------------------------------
# the bodies of the readers that every cell kind shares (``bench/metrics``);
# ``r`` is ``run.Readings``
# ---------------------------------------------------------------------------


def mfu(r):
    """Model FLOP utilization of the whole step, in %: the model FLOPs of
    the tokens the driver counts as trained (``tokens``) over the host time
    they took (``trained_seconds``), the chips and the bf16 peak."""
    ctx = r.ctx
    if not ctx["tokens"] or not ctx["trained_seconds"]:
        return None
    done = ctx["tokens"] * ctx["flops_per_token"]
    return 100.0 * done / (ctx["trained_seconds"] * r.chips
                           * r.peaks["bf16_flops"])


def flash_roofline(r):
    """The Pallas flash-attention forward's share of its roofline, in %:
    the least time of its causal operations and bytes at the driver's
    attention shape (``attention``), over the time each call took in the
    trace, summed over calls and chips.  A call is one op named
    ``closed_call`` (the ``custom_vjp`` body it lowers from) or
    ``tpu_custom_call``, with the head-major output
    bf16[batch, heads, seq, head_dim]."""
    from bench.lib import trace as tr
    a = r.ctx["attention"]
    out = "= bf16[{},{},{},{}]".format(a["batch"], a["heads"], a["seq"],
                                       a["head_dim"])

    def match(name):
        return ("closed_call" in name or "tpu_custom_call" in name) \
            and out in name

    rows = tr.events(r.trace, "ops", match, r.lo, r.hi)
    if not rows:
        return None
    ops, nbytes = flash_forward(a["batch"], a["seq"], a["heads"],
                                a["kv_heads"], a["head_dim"])
    least, _ = least_seconds(ops, nbytes, r.peaks)
    took = sum(row[2] for _, row in rows) / 1e9
    return 100.0 * least * len(rows) / took
