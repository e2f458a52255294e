"""Blockwise flash attention (forward) Pallas TPU kernel.

Grid layout (batch, q_head, q_block, kv_block); the kv_block axis is the
innermost, sequentially-iterated ("arbitrary") dimension, so the VMEM
scratch carrying the online-softmax running state (m, l, acc) persists
across kv steps and the output block is written once on the last step.
GQA folds into the K/V index maps (q head h reads kv head h // group).

VMEM budget per step at the default tiling (bq = bkv = 512, D = 128):
q/k/v blocks 3 * 512*128*2B = 384 KiB + fp32 acc 512*128*4B = 256 KiB —
comfortably inside the ~16 MiB/core budget, with the MXU seeing
(512x128)@(128x512) contractions (both dims 128-aligned).

Causality is enforced with an in-block mask; fully-masked kv blocks are
skipped via ``pl.when`` (the q_offset shift supports decode-style calls).
Backward runs through ``jax.custom_vjp`` against the jnp oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common import cdiv
from repro.kernels import ref

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, q_offset: int,
                  bq: int, bkv: int, n_kv: int):
    iq = pl.program_id(2)
    ikv = pl.program_id(3)

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = iq * bq + q_offset               # absolute position of q row 0
    kv_lo = ikv * bkv
    # skip kv blocks strictly above the causal diagonal
    run = (kv_lo <= q_lo + bq - 1) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bkv, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = q @ k.T                                          # (bq, bkv)
        if causal:
            qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            kpos = kv_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + p @ v
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ikv == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def _flash_call(q, k, v, *, causal, q_offset, scale, interpret,
                bq=512, bkv=512):
    """q (B, H, Sq, D), k/v (B, KH, Skv, D) -> (B, H, Sq, D)."""
    B, H, Sq, D = q.shape
    _, KH, Skv, _ = k.shape
    G = H // KH
    bq = min(bq, Sq)
    bkv = min(bkv, Skv)
    n_kv = cdiv(Skv, bkv)
    grid = (B, H, cdiv(Sq, bq), n_kv)

    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          q_offset=q_offset, bq=bq, bkv=bkv, n_kv=n_kv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)


@functools.lru_cache(maxsize=None)
def _flash_fn(causal: bool, q_offset: int, scale: float, interpret: bool):
    @jax.custom_vjp
    def f(q, k, v):
        # (B, S, H, D) -> (B, H, S, D) for contiguous blocking
        qt = q.transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        o = _flash_call(qt, kt, vt, causal=causal, q_offset=q_offset,
                        scale=scale, interpret=interpret)
        return o.transpose(0, 2, 1, 3)

    def fwd(q, k, v):
        return f(q, k, v), (q, k, v)

    def bwd(res, g):
        _, vjp = jax.vjp(
            lambda q, k, v: ref.attention(
                q, k, v, causal=causal, q_offset=q_offset,
                softmax_scale=scale), *res)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f


def flash_attention(q, k, v, *, causal=True, q_offset=0, softmax_scale=None,
                    interpret=False):
    """Drop-in for ref.attention (without kv_len masking): q (B,Sq,H,D)."""
    D = q.shape[-1]
    scale = float(softmax_scale if softmax_scale is not None
                  else 1.0 / np.sqrt(D))
    return _flash_fn(bool(causal), int(q_offset), scale, bool(interpret))(
        q, k, v)
