"""A model family that lives beside the tests and whose layers differ by
their place in the model: the dense decoder in which the last layer of
every period of three (model-wide layers 2, 5, 8, ...) has no FFN, as a
hybrid period or leading dense layers make layer kinds depend on place.
The program has no such layer, so only the references run it:
``test_families.py`` checks that the pipeline's stage-by-stage reference,
which hands ``blocks`` one layer at a time with its model-wide index,
computes the loss of the whole-model reference."""
from __future__ import annotations

import jax

from bench.lib.reference import rmsnorm
from bench.models import dense_decoder as dense

program_config = dense.program_config
init_leaf = dense.init_leaf
block_flops_per_token = dense.block_flops_per_token
attention_shape = dense.attention_shape
PERIOD = 3


def blocks(pb, x, m, mode, first):
    eps = m["norm_eps"]
    for layer in range(jax.tree.leaves(pb)[0].shape[0]):
        p = jax.tree.map(lambda a: a[layer], pb)
        x = x + dense.attention(p["attn"], rmsnorm(x, p["attn_norm"], eps),
                                m, mode)
        if (first + layer) % PERIOD != PERIOD - 1:
            x = x + dense.mlp(p["mlp"], rmsnorm(x, p["ffn_norm"], eps), mode)
    return x
