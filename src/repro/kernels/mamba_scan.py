"""Selective-scan (Mamba) Pallas TPU kernel — §Perf cell B.

The jamba-v0.1-52b train_4k cell is memory-bound on the sequential SSM
scan: in the compiled HLO the (B, d_inner, d_state) carry h round-trips HBM
every timestep (~34 GB/layer/microbatch).  This kernel keeps h resident in
VMEM scratch and streams the per-timestep inputs once:

  grid = (B, d_inner/bd, S/bs)   — the S dimension iterates sequentially
  scratch: h (bd, d_state) fp32  — persists across S blocks
  per step t:  dA = exp(delta_t (x) A);  h = dA * h + (delta_t * x_t) (x) B_t
               y_t = h . C_t + D * x_t

HBM traffic drops to one read of (delta, x, B, C) + one write of y:
~8 bytes/element/timestep vs ~2 * d_state * 4 for the carry round-trip —
a ~16x reduction of the dominant term (EXPERIMENTS.md §Perf cell B).

Validated in interpret mode against the ref scan (tests/test_kernels.py);
backward via custom_vjp over the reference formulation in ops.py style.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common import cdiv

DEFAULT_BD = 256          # d_inner block
DEFAULT_BS = 512          # sequence block


def _mamba_kernel(delta_ref, x_ref, b_ref, c_ref, a_ref, y_ref, h_scr, *,
                  bs: int, bd: int, ds: int):
    js = pl.program_id(2)

    @pl.when(js == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[...].astype(jnp.float32)                   # (bd, ds)
    delta = delta_ref[...].reshape(bs, bd).astype(jnp.float32)   # VMEM block
    x = x_ref[...].reshape(bs, bd).astype(jnp.float32)
    b = b_ref[...].reshape(bs, ds).astype(jnp.float32)
    c = c_ref[...].reshape(bs, ds).astype(jnp.float32)

    def step(t, carry):
        h, y = carry
        delta_t = jax.lax.dynamic_index_in_dim(delta, t, 0, keepdims=False)
        x_t = jax.lax.dynamic_index_in_dim(x, t, 0, keepdims=False)
        b_t = jax.lax.dynamic_index_in_dim(b, t, 0, keepdims=False)
        c_t = jax.lax.dynamic_index_in_dim(c, t, 0, keepdims=False)
        dA = jnp.exp(delta_t[:, None] * a)                # (bd, ds)
        h = dA * h + (delta_t * x_t)[:, None] * b_t[None, :]
        y_t = jnp.sum(h * c_t[None, :], axis=1)           # (bd,)
        y = jax.lax.dynamic_update_index_in_dim(y, y_t, t, 0)
        return h, y

    y0 = jnp.zeros((bs, delta.shape[1]), jnp.float32)
    h, y = jax.lax.fori_loop(0, bs, step, (h_scr[...], y0))
    h_scr[...] = h
    y_ref[0] = y.astype(y_ref.dtype)


def mamba_scan(delta, x, b_ssm, c_ssm, a, *, interpret: bool = False,
               bd: int = DEFAULT_BD, bs: int = DEFAULT_BS):
    """delta/x (B, S, d_in) f32; b_ssm/c_ssm (B, S, ds) f32; a (d_in, ds).

    Returns y (B, S, d_in) f32 with y_t = C_t . h_t (caller adds D*x and
    gating).  Forward-only; wrap with a custom_vjp against the ref scan for
    training (see ops.mamba_scan).
    """
    B, S, d_in = delta.shape
    ds = b_ssm.shape[-1]
    bd_ = min(bd, d_in)
    bs_ = min(bs, S)
    grid = (B, cdiv(d_in, bd_), cdiv(S, bs_))

    return pl.pallas_call(
        functools.partial(_mamba_kernel, bs=bs_, bd=bd_, ds=ds),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bs_, bd_), lambda i, j, s: (i, s, j)),
            pl.BlockSpec((1, bs_, bd_), lambda i, j, s: (i, s, j)),
            pl.BlockSpec((1, bs_, ds), lambda i, j, s: (i, s, 0)),
            pl.BlockSpec((1, bs_, ds), lambda i, j, s: (i, s, 0)),
            pl.BlockSpec((bd_, ds), lambda i, j, s: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs_, bd_), lambda i, j, s: (i, s, j)),
        out_shape=jax.ShapeDtypeStruct((B, S, d_in), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bd_, ds), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(delta, x, b_ssm, c_ssm, a)


def mamba_scan_ref(delta, x, b_ssm, c_ssm, a):
    """Pure-jnp oracle (the same recurrence models/mamba.py runs).

    Uses the remat-chunked scan (scan_utils) so the CPU/compiled path keeps
    the bounded carry-storage behaviour the model had before the kernel was
    introduced — a plain lax.scan saves per-step residuals for backward and
    quadruples the jamba train memory term (§Perf cell B measurement)."""
    B, S, d_in = delta.shape

    def step(h, ins):
        delta_t, x_t, b_t, c_t = ins
        dA = jnp.exp(delta_t[..., None] * a[None])
        h = dA * h + (delta_t * x_t)[..., None] * b_t[:, None, :]
        y = jnp.einsum("bds,bs->bd", h, c_t)
        return h, y

    from repro.models.scan_utils import chunked_scan, pick_chunk
    h0 = jnp.zeros((B, d_in, a.shape[-1]), jnp.float32)
    _, ys = chunked_scan(
        step, h0,
        (delta.transpose(1, 0, 2), x.transpose(1, 0, 2),
         b_ssm.transpose(1, 0, 2), c_ssm.transpose(1, 0, 2)),
        chunk=pick_chunk(S))
    return ys.transpose(1, 0, 2)
