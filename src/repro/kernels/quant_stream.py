"""int8 blockwise stream codec Pallas kernels (compressed-sharing stage).

Weights/optimizer deltas are quantized on the way into the StateStore
(paper §2 stage 2).  Symmetric per-block int8: each 256-element block gets
one fp32 scale (amax/127).  The kernels tile the flat vector into
lane-dense (rows x 128) panels so quantize+scale extraction happen in one
VMEM pass, with no relayout of the vector around the kernel.
Not differentiated (codec runs outside the autodiff graph).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common import cdiv

BLOCK = 256
LANES = 128
# Scales per grid step.  They leave as a 1-D block, which XLA tiles in
# units of 1024: a smaller block is refused on TPU once the vector spans
# more than one step.  At BLOCK = 256 a step reads a 1 MiB fp32 panel.
BLOCKS_PER_STEP = 1024


def _view(n: int, block: int) -> tuple[int, int]:
    """(width, rows-per-block) of the 2-D view a codec kernel tiles.

    A block that is a whole number of 128-lane rows is viewed lane-dense,
    as (n / 128, 128): that view and XLA's tiled layout of the flat vector
    are the same bytes, so neither the fp32 input nor the int8 codes are
    relaid out around the kernel.  Block b then spans b / 128 consecutive
    rows, which the kernel gathers with stride-b/128 loads.  Any other
    block (short wire-code rows) is viewed as (n / block, block)."""
    width = LANES if block % LANES == 0 else block
    return width, block // width


def _row_scales(scale, k: int, scr):
    """Repeat each block's (rp, 1) scale over its k view rows, into scr."""
    rp = scale.shape[0]
    wide = jnp.broadcast_to(scale, (rp, scr.shape[1]))
    for p in range(k):
        scr[pl.ds(p, rp, stride=k), :] = wide
    return scr[...]


def _quant_kernel(x_ref, q_ref, s_ref, scr, *, k: int):
    rp = s_ref.shape[0]
    amax = None
    for p in range(k):
        part = x_ref[pl.ds(p, rp, stride=k), :].astype(jnp.float32)
        m = jnp.max(jnp.abs(part), axis=1, keepdims=True)
        amax = m if amax is None else jnp.maximum(amax, m)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)              # (rp, 1)
    x = x_ref[...].astype(jnp.float32)
    q = jnp.clip(jnp.round(x / _row_scales(scale, k, scr)), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale[:, 0]


def quantize_int8(x, block: int = BLOCK, interpret: bool = False):
    (n,) = x.shape
    assert n % block == 0, (n, block)
    width, k = _view(n, block)
    n_blocks = n // block
    rp = min(BLOCKS_PER_STEP, n_blocks)
    q, s = pl.pallas_call(
        functools.partial(_quant_kernel, k=k),
        grid=(cdiv(n_blocks, rp),),
        in_specs=[pl.BlockSpec((k * rp, width), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((k * rp, width), lambda i: (i, 0)),
                   pl.BlockSpec((rp,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((n // width, width), jnp.int8),
                   jax.ShapeDtypeStruct((n_blocks,), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((k * rp, width), jnp.float32)],
        interpret=interpret,
        name="quantize_int8",
    )(x.reshape(n // width, width))
    return q.reshape(n), s


def _dequant_kernel(q_ref, s_ref, o_ref, scr, *, k: int):
    scale = s_ref[...][:, None]                                 # (rp, 1)
    o_ref[...] = q_ref[...].astype(jnp.float32) * _row_scales(scale, k, scr)


def dequantize_int8(q, scales, block: int = BLOCK, interpret: bool = False):
    (n,) = q.shape
    width, k = _view(n, block)
    n_blocks = n // block
    rp = min(BLOCKS_PER_STEP, n_blocks)
    out = pl.pallas_call(
        functools.partial(_dequant_kernel, k=k),
        grid=(cdiv(n_blocks, rp),),
        in_specs=[pl.BlockSpec((k * rp, width), lambda i: (i, 0)),
                  pl.BlockSpec((rp,), lambda i: (i,))],
        out_specs=pl.BlockSpec((k * rp, width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n // width, width), jnp.float32),
        scratch_shapes=[pltpu.VMEM((k * rp, width), jnp.float32)],
        interpret=interpret,
        name="dequantize_int8",
    )(q.reshape(n // width, width), scales)
    return out.reshape(n)


# ---------------------------------------------------------------------------
# int8 pipeline wire codec (paper §4: 128x on-wire = 64x bottleneck x 2x
# int8-vs-bf16).  Bottleneck codes are quantized at stage exit and
# dequantized at stage entry; gradients crossing the wire backward are
# quantized symmetrically (the straight-through custom_vjp below), so the
# compression is the paper's symmetrical headline number.
# ---------------------------------------------------------------------------


def wire_block(n: int, last_dim: int) -> int:
    """Block size for an n-element code tensor (mirrors ref.wire_code_block):
    the standard 256-element block when it divides, else one scale per code
    row — the trailing bottleneck dim always divides the element count."""
    return BLOCK if n % BLOCK == 0 else last_dim


def quantize_wire(z, interpret: bool = False):
    """(..., d_b) code tensor -> (q int8 same-shape, scales f32, block)."""
    n = z.size
    blk = wire_block(n, z.shape[-1])
    q, s = quantize_int8(z.astype(jnp.float32).reshape(-1), block=blk,
                         interpret=interpret)
    return q.reshape(z.shape), s, blk


def dequantize_wire(q, scales, block: int, interpret: bool = False):
    out = dequantize_int8(q.reshape(-1), scales, block=block,
                          interpret=interpret)
    return out.reshape(q.shape)


def wire_nbytes(shape, block: int | None = None) -> int:
    """Honest on-wire bytes for an int8-coded tensor: int8 payload + one
    fp32 scale per block."""
    n = 1
    for dim in shape:
        n *= dim
    blk = block or wire_block(n, shape[-1])
    return n + (n // blk) * 4


@functools.lru_cache(maxsize=None)
def _roundtrip_fn(interpret: bool):
    def rt(z):
        q, s, blk = quantize_wire(z, interpret=interpret)
        return dequantize_wire(q, s, blk, interpret=interpret).astype(z.dtype)

    @jax.custom_vjp
    def f(z):
        return rt(z)

    def fwd(z):
        return f(z), None

    def bwd(_, g):
        # backward wire codes are int8 too (paper's symmetric compression);
        # the quantizer itself is straight-through
        return (rt(g),)

    f.defvjp(fwd, bwd)
    return f


def int8_wire_roundtrip(z, interpret: bool = False):
    """Differentiable fake-quant of the pipeline wire: forward sees exactly
    the dequantized int8 code the receiving stage would see; the cotangent
    is quantized the same way on the way back.  Numerically identical to
    physically shipping (int8, scales) in both directions."""
    return _roundtrip_fn(bool(interpret))(z)
