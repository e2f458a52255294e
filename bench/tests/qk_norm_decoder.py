"""A model family that lives beside the tests: the dense decoder with
RMSNorm over each query and key head before rotary (QK-norm, as OLMo-2 and
Qwen3 have it), which the program runs as its dense family with
``qk_norm`` on.  ``test_families.py`` runs a swarm cell on it with no file
outside ``bench/tests/`` touched, which is what a new family costs."""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from bench.lib.reference import einsum, rmsnorm
from bench.models import dense_decoder as dense

init_leaf = dense.init_leaf
block_flops_per_token = dense.block_flops_per_token
attention_shape = dense.attention_shape


def program_config(name: str, m: dict):
    return dataclasses.replace(dense.program_config(name, m), qk_norm=True)


def attention(p, x, m, mode):
    B, S, _ = x.shape
    H, KH, D = m["num_attention_heads"], m["num_key_value_heads"], \
        m["head_dim"]
    G, eps = H // KH, m["norm_eps"]
    q = einsum("bsd,de->bse", x, p["wq"], mode).reshape(B, S, H, D)
    k = einsum("bsd,de->bse", x, p["wk"], mode).reshape(B, S, KH, D)
    v = einsum("bsd,de->bse", x, p["wv"], mode).reshape(B, S, KH, D)
    q = dense.rotary(rmsnorm(q, p["q_norm"], eps), m["rope_theta"])
    k = dense.rotary(rmsnorm(k, p["k_norm"], eps), m["rope_theta"])
    q = q.reshape(B, S, KH, G, D)
    s = einsum("bqkgd,bskd->bkgqs", q, k, mode) / math.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(s, axis=-1), v,
               mode).reshape(B, S, H * D)
    return einsum("bse,ed->bsd", o, p["wo"], mode)


def blocks(pb, x, m, mode, first):
    eps = m["norm_eps"]
    for layer in range(jax.tree.leaves(pb)[0].shape[0]):
        p = jax.tree.map(lambda a: a[layer], pb)
        x = x + attention(p["attn"], rmsnorm(x, p["attn_norm"], eps), m, mode)
        x = x + dense.mlp(p["mlp"], rmsnorm(x, p["ffn_norm"], eps), mode)
    return x
