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


def matmul_params_per_token(m: dict, n_layers: int, bottleneck_dim: int,
                            n_boundaries: int) -> int:
    """Matrix-product weights a token passes through: the blocks, the
    unembedding over the real vocabulary, and one bottleneck encode and
    decode per stage boundary."""
    d, H, KH, D = (m["hidden_size"], m["num_attention_heads"],
                   m["num_key_value_heads"], m["head_dim"])
    attn = d * H * D * 2 + d * KH * D * 2              # wq, wo, wk, wv
    ffn = 3 * d * m["intermediate_size"]               # gate, up, out
    boundary = 2 * d * bottleneck_dim                  # w_down + w_up
    return (n_layers * (attn + ffn) + m["vocab_size"] * d
            + n_boundaries * boundary)


def train_flops_per_token(m: dict, n_layers: int, seq_len: int,
                          bottleneck_dim: int, n_boundaries: int) -> float:
    H, D = m["num_attention_heads"], m["head_dim"]
    dense = 6 * matmul_params_per_token(m, n_layers, bottleneck_dim,
                                        n_boundaries)
    attn = 6 * seq_len * H * D * n_layers
    return float(dense + attn)


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
