"""Masked shard-mean Pallas kernel — the butterfly reduce inner loop.

A reducer averages one weight shard across all N miners' uploads, skipping
miners whose upload is missing/invalid (paper §5.2 failure handling).  The
kernel tiles the shard into VMEM panels and computes the masked mean in one
pass: a masked sum over the miner axis (the (M, 1) validity column
broadcast along lanes, then a sublane reduce — Mosaic lowers no
matrix-vector einsum of this shape), divided by the valid count.  Not differentiated (merge runs outside the autodiff graph).

Callers go through the ``kernels.ops.shard_merge`` dispatch (compiled here
on TPU, ``ref.shard_merge`` oracle on CPU, ``REPRO_FORCE_PALLAS_INTERPRET=1``
honored); the ``interpret`` flag below exists for the kernel equivalence
suite only, like every other kernel module.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.common import cdiv

COLS_PER_STEP = 16384        # 16 miners x 16k fp32 = 1 MiB per panel


def _merge_kernel(shards_ref, valid_ref, o_ref):
    shards = shards_ref[...].astype(jnp.float32)         # (M, cols)
    valid = valid_ref[...]                               # (M, 1) fp32
    num = jnp.sum(shards * valid, axis=0)
    den = jnp.maximum(jnp.sum(valid), 1.0)
    o_ref[...] = num / den


def shard_merge(shards, valid, interpret: bool = False):
    M, L = shards.shape
    cols = min(COLS_PER_STEP, L)
    return pl.pallas_call(
        _merge_kernel,
        grid=(cdiv(L, cols),),
        in_specs=[pl.BlockSpec((M, cols), lambda i: (0, i)),
                  pl.BlockSpec((M, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((cols,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((L,), jnp.float32),
        interpret=interpret,
        name="shard_merge",
    )(shards, valid.astype(jnp.float32).reshape(M, 1))
