"""Cached-decode attention Pallas TPU kernel (the serve plane's hot loop).

The flash kernel blocks over (q, kv) for train/prefill shapes; decode is
the opposite regime — one (or a few prefill) query rows against a long KV
cache *buffer* whose valid prefix length is dynamic (``kv_len`` = cache
length + the rows being appended this step).  Grid is (batch, q_head,
kv_block) with the kv axis innermost/sequential, so the online-softmax
running state (m, l, acc) lives in VMEM scratch across kv steps and the
(small) output block is written once on the last step.  Cache blocks past
the valid prefix are skipped entirely (``pl.when`` on the dynamic bound);
inside a live block both the causal mask (``kpos <= q_offset + row``) and
the prefix mask (``kpos < kv_len``) apply, exactly ``ref.attention``'s
semantics with ``causal=True`` and a ``kv_len``.

``kv_len``/``q_offset`` are traced per-batch scalars (they ride the KV
cache state through jit), shipped to the kernel as one (B, 2) int32
scalar-prefetch operand — scalars steer control flow, so they must live in
SMEM, and a whole-array prefetch sidesteps the (8, 128) block rule that a
per-lane (1, 2) SMEM block would break.

Inference-only: no ``custom_vjp`` — the serve plane never differentiates,
and ``ops.flash_attention`` routes autodiff-bearing shapes (no cache) to
the flash/ref paths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common import cdiv

NEG_INF = -1e30


def _decode_kernel(meta_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *,
                   scale: float, bq: int, bkv: int, n_kv: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    b = pl.program_id(0)
    kv_len = meta_ref[b, 0]
    q_off = meta_ref[b, 1]
    kv_lo = j * bkv

    # blocks entirely past the valid prefix contribute nothing
    @pl.when(kv_lo < kv_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bkv, D)
        # a cache buffer that is not a whole number of kv blocks leaves the
        # tail of its last block unwritten: zero those rows so p @ v never
        # multiplies a zero weight by whatever bits sit there
        vpos = kv_lo + jax.lax.broadcasted_iota(jnp.int32, (bkv, 1), 0)
        v = jnp.where(vpos < kv_len, v_ref[0, 0].astype(jnp.float32), 0.0)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        qpos = q_off + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kpos = kv_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        live = (kpos <= qpos) & (kpos < kv_len)
        s = jnp.where(live, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + p @ v
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(j == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention(q, k, v, *, q_offset, kv_len, softmax_scale=None,
                     interpret=False, bkv=512):
    """GQA attention over a KV cache buffer: q (B, Sq, H, D) against
    k/v (B, S_max, KH, D) with per-batch valid length ``kv_len`` (B,) and
    absolute first-row position ``q_offset`` (scalar or (B,)).  Matches
    ``ref.attention(..., causal=True, q_offset=..., kv_len=...)``."""
    B, Sq, H, D = q.shape
    _, Skv, KH, _ = k.shape
    G = H // KH
    scale = float(softmax_scale if softmax_scale is not None
                  else 1.0 / np.sqrt(D))
    meta = jnp.stack([
        jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (B,)),
        jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (B,)),
    ], axis=1)                                               # (B, 2) int32

    qt = q.transpose(0, 2, 1, 3)                             # (B, H, Sq, D)
    kt = k.transpose(0, 2, 1, 3)                             # (B, KH, Skv, D)
    vt = v.transpose(0, 2, 1, 3)
    bkv = min(bkv, Skv)
    n_kv = cdiv(Skv, bkv)
    grid = (B, H, n_kv)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Sq, D), lambda b, h, j, m: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, j, m: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, j, m: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Sq, D),
                               lambda b, h, j, m: (b, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((Sq,), jnp.float32),
                        pltpu.VMEM((Sq,), jnp.float32),
                        pltpu.VMEM((Sq, D), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, bq=Sq, bkv=bkv,
                          n_kv=n_kv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(meta, qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
