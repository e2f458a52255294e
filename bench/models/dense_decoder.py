"""The dense decoder: pre-norm GQA attention and a SwiGLU FFN, RMSNorm,
rotary over the whole head.  The program runs it as its ``dense`` family
(``attn_dense`` blocks).  The contract is ``bench/models/__init__.py``'s.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.lib.reference import einsum, rmsnorm


def program_config(name: str, m: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        arch_id=name, family="dense", n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"], d_head=m["head_dim"],
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["norm_eps"]))


def init_leaf(key, name: str, shape, dtype):
    """Norm gains 1, the decode gate alpha 0.5 (a scalar a stage, or one
    per stage where stages are stacked), the embedding N(0, 1), every
    matrix N(0, 1 / fan-in), where the unembedding's fan-in is its last
    axis."""
    last = name.rsplit("/", 1)[-1]
    if "norm" in last:
        return jnp.ones(shape, dtype)
    if len(shape) == 0 or last == "alpha_dec":
        return jnp.full(shape, 0.5, dtype)
    if last == "embed":
        scale = 1.0
    elif last == "unembed":
        scale = 1.0 / math.sqrt(shape[-1])
    else:
        scale = 1.0 / math.sqrt(shape[-2])
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def rotary(x, theta: float):
    """Rotary position embedding over the whole head, split-half pairing:
    dimension i rotates with i + D/2 at frequency theta^(-i / (D/2))."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv        # (S, D/2)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, x, m, mode):
    B, S, _ = x.shape
    H, KH, D = m["num_attention_heads"], m["num_key_value_heads"], \
        m["head_dim"]
    G = H // KH
    q = einsum("bsd,de->bse", x, p["wq"], mode).reshape(B, S, KH, G, D)
    k = einsum("bsd,de->bse", x, p["wk"], mode).reshape(B, S, KH, D)
    v = einsum("bsd,de->bse", x, p["wv"], mode).reshape(B, S, KH, D)
    q = rotary(q.reshape(B, S, H, D), m["rope_theta"]).reshape(B, S, KH, G, D)
    k = rotary(k, m["rope_theta"])
    s = einsum("bqkgd,bskd->bkgqs", q, k, mode) / math.sqrt(D)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = einsum("bkgqs,bskd->bqkgd", w, v, mode).reshape(B, S, H * D)
    return einsum("bse,ed->bsd", o, p["wo"], mode)


def mlp(p, x, mode):
    h = jax.nn.silu(einsum("bsd,df->bsf", x, p["w_gate"], mode)) \
        * einsum("bsd,df->bsf", x, p["w_up"], mode)
    return einsum("bsf,fd->bsd", h, p["w_out"], mode)


def blocks(pb, x, m, mode, first):
    """Every layer alike: ``first`` is not read."""
    eps = m["norm_eps"]
    n_layers = jax.tree.leaves(pb)[0].shape[0]
    for layer in range(n_layers):
        p = jax.tree.map(lambda a: a[layer], pb)
        x = x + attention(p["attn"], rmsnorm(x, p["attn_norm"], eps), m, mode)
        x = x + mlp(p["mlp"], rmsnorm(x, p["ffn_norm"], eps), mode)
    return x


def block_flops_per_token(m: dict, n_layers: int, seq_len: int) -> int:
    """6 x the attention and FFN weights a token passes, plus causal
    attention's 6 * S * H * D a layer."""
    d, H, KH, D = (m["hidden_size"], m["num_attention_heads"],
                   m["num_key_value_heads"], m["head_dim"])
    attn = d * H * D * 2 + d * KH * D * 2              # wq, wo, wk, wv
    ffn = 3 * d * m["intermediate_size"]               # gate, up, out
    return 6 * n_layers * (attn + ffn) + 6 * seq_len * H * D * n_layers


def attention_shape(m: dict, batch: int, seq: int) -> dict:
    return dict(batch=batch, seq=seq, heads=m["num_attention_heads"],
                kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"])
