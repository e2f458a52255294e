"""Public jit'd entry points for the Pallas kernels.

Dispatch policy:
  * On a TPU backend the Pallas kernels run compiled.  There is no fallback:
    a kernel the chip's compiler refuses is an error, never a quiet switch
    to the reference.
  * On a non-TPU backend (CPU runs and tests) the pure-jnp reference in
    ``kernels/ref.py`` runs — unless ``REPRO_FORCE_PALLAS_INTERPRET=1``,
    which runs the actual kernel bodies under ``interpret=True`` (used by
    kernel tests).

Models call ONLY these wrappers, never the kernels directly.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import ref


def _use_pallas() -> bool:
    return (jax.default_backend() == "tpu"
            or os.environ.get("REPRO_FORCE_PALLAS_INTERPRET") == "1")


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------


def flash_attention(q, k, v, *, causal=True, q_offset=0, kv_len=None,
                    softmax_scale=None):
    """GQA attention; Pallas flash kernel on TPU, oracle elsewhere.

    The backward pass always differentiates the reference formulation (the
    kernel is wrapped in ``jax.custom_vjp`` whose bwd re-runs the oracle's
    VJP) — forward speed is where the kernel matters for train/prefill.

    Off-TPU long sequences use the streaming jnp formulation
    (``ref.attention_chunked``) so the compiled graph never materializes the
    S^2 probability matrix — §Perf change #1, adopted globally after
    confirmation on the llama3.2-1b train_4k cell (EXPERIMENTS.md §Perf).
    """
    if _use_pallas() and kv_len is None and q.shape[1] > 1:
        from repro.kernels import flash_attention as fa
        return fa.flash_attention(
            q, k, v, causal=causal, q_offset=q_offset,
            softmax_scale=softmax_scale, interpret=_interpret())
    if _use_pallas() and kv_len is not None and causal:
        # cached decode/prefill: dynamic valid-prefix length, tiny q block —
        # the kv-streaming kernel (inference-only; no vjp, see its module)
        from repro.kernels import decode_attention as da
        return da.decode_attention(
            q, k, v, q_offset=q_offset, kv_len=kv_len,
            softmax_scale=softmax_scale, interpret=_interpret())
    # §Perf finding (EXPERIMENTS.md): expressing the flash schedule as jnp
    # scans INCREASES HLO-level traffic (block tensors + carries still round
    # -trip HBM in the compiled graph; only a real kernel boundary keeps
    # them in VMEM).  The chunked path is therefore opt-in for experiments;
    # the roofline instead reports the kernel substitution via the measured
    # attention-interior bytes (launch/hlo_cost.py).
    if (kv_len is None and q.shape[1] >= 1024
            and os.environ.get("REPRO_CHUNKED_ATTN") == "1"):
        return ref.attention_chunked(
            q, k, v, causal=causal, q_offset=q_offset,
            softmax_scale=softmax_scale)
    return ref.attention(q, k, v, causal=causal, q_offset=q_offset,
                         kv_len=kv_len, softmax_scale=softmax_scale)


# ---------------------------------------------------------------------------
# Bottleneck encode / decode (paper §4 compression hot-spot)
# ---------------------------------------------------------------------------


def bottleneck_encode(x, gamma, w_down, *, eps=1e-5, wire_dtype=jnp.bfloat16):
    if _use_pallas():
        from repro.kernels import bottleneck_fused as bf
        return bf.bottleneck_encode(x, gamma, w_down, eps=eps,
                                    wire_dtype=wire_dtype,
                                    interpret=_interpret())
    return ref.bottleneck_encode(x, gamma, w_down, eps=eps, wire_dtype=wire_dtype)


def bottleneck_decode(z, w_up, residual, alpha, *, out_dtype=jnp.bfloat16):
    if _use_pallas():
        from repro.kernels import bottleneck_fused as bf
        return bf.bottleneck_decode(z, w_up, residual, alpha,
                                    out_dtype=out_dtype, interpret=_interpret())
    return ref.bottleneck_decode(z, w_up, residual, alpha, out_dtype=out_dtype)


def bottleneck_decode_gated(z, w_up, alpha, *, out_dtype=jnp.bfloat16):
    """Pipeline stage-entry decode: alpha * (z @ W_up), fused on TPU."""
    if _use_pallas():
        from repro.kernels import bottleneck_fused as bf
        return bf.bottleneck_decode_gated(z, w_up, alpha,
                                          out_dtype=out_dtype,
                                          interpret=_interpret())
    return ref.bottleneck_decode_gated(z, w_up, alpha, out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# int8 stream codec
# ---------------------------------------------------------------------------


def quantize_int8(x, block: int = 256):
    if _use_pallas():
        from repro.kernels import quant_stream as qs
        return qs.quantize_int8(x, block=block, interpret=_interpret())
    return ref.quantize_int8(x, block=block)


def dequantize_int8(q, scales, block: int = 256):
    if _use_pallas():
        from repro.kernels import quant_stream as qs
        return qs.dequantize_int8(q, scales, block=block, interpret=_interpret())
    return ref.dequantize_int8(q, scales, block=block)


@jax.custom_vjp
def _ref_wire_roundtrip(z):
    return ref.int8_wire_roundtrip(z)


def _ref_wire_fwd(z):
    return _ref_wire_roundtrip(z), None


def _ref_wire_bwd(_, g):
    # backward wire codes quantize symmetrically (straight-through)
    return (ref.int8_wire_roundtrip(g),)


_ref_wire_roundtrip.defvjp(_ref_wire_fwd, _ref_wire_bwd)


def int8_wire_roundtrip(z):
    """Differentiable int8 fake-quant of the pipeline wire (see
    quant_stream.int8_wire_roundtrip); kernel on TPU, oracle elsewhere —
    both quantize the cotangent on the way back."""
    if _use_pallas():
        from repro.kernels import quant_stream as qs
        return qs.int8_wire_roundtrip(z, interpret=_interpret())
    return _ref_wire_roundtrip(z)


def wire_encode(z):
    """Quantize a wire-code tensor into the physically shipped/stashed
    (int8 codes, fp32 scales) pair.  ``wire_decode(*wire_encode(z))`` is
    bit-identical to ``int8_wire_roundtrip(z)`` in f32 — both compose the
    same quantize/dequantize with the same wire block — so the slot
    executor can keep the compressed pair in its stash rings without
    changing numerics.  Not differentiated (the executor quantizes outside
    its vjps, exactly where the old roundtrip sat)."""
    if _use_pallas():
        from repro.kernels import quant_stream as qs
        q, s, _ = qs.quantize_wire(z, interpret=_interpret())
        return q, s
    blk = ref.wire_code_block(z.size, z.shape[-1])
    q, s = ref.quantize_int8(z.astype(jnp.float32).reshape(-1), block=blk)
    return q.reshape(z.shape), s


def wire_decode(q, scales):
    """Exact f32 dequantization of a ``wire_encode`` pair (q * scale)."""
    blk = ref.wire_code_block(q.size, q.shape[-1])
    if _use_pallas():
        from repro.kernels import quant_stream as qs
        return qs.dequantize_wire(q, scales, blk, interpret=_interpret())
    return ref.dequantize_int8(
        q.reshape(-1), scales, block=blk).reshape(q.shape)


# ---------------------------------------------------------------------------
# Butterfly shard merge
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _shard_merge_fn(use_pallas: bool, interpret: bool):
    if use_pallas:
        from repro.kernels import shard_merge as sm
        return jax.jit(functools.partial(sm.shard_merge,
                                         interpret=interpret))
    return jax.jit(ref.shard_merge)


def shard_merge(shards, valid):
    """Masked shard mean — the butterfly reduce inner loop.  Jit-cached:
    the store-and-forward executor calls this once per shard, and a plan's
    near-equal bounds produce at most two distinct shard widths, so every
    reduce after the first two hits the compile cache."""
    if _use_pallas():
        return _shard_merge_fn(True, _interpret())(shards, valid)
    return _shard_merge_fn(False, False)(shards, valid)


# ---------------------------------------------------------------------------
# Mamba selective scan (§Perf cell B kernel)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _mamba_scan_fn(interpret: bool):
    from repro.kernels import mamba_scan as ms

    @jax.custom_vjp
    def f(delta, x, b_ssm, c_ssm, a):
        return ms.mamba_scan(delta, x, b_ssm, c_ssm, a, interpret=interpret)

    def fwd(delta, x, b_ssm, c_ssm, a):
        return f(delta, x, b_ssm, c_ssm, a), (delta, x, b_ssm, c_ssm, a)

    def bwd(res, g):
        _, vjp = jax.vjp(ms.mamba_scan_ref, *res)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f


def mamba_scan(delta, x, b_ssm, c_ssm, a):
    """Selective-scan y_t = C_t . h_t; Pallas kernel on TPU (h stays in

    VMEM — the §Perf cell B fix for the scan-carry HBM traffic), reference
    lax.scan elsewhere."""
    if _use_pallas():
        return _mamba_scan_fn(_interpret())(delta, x, b_ssm, c_ssm, a)
    from repro.kernels import mamba_scan as ms
    return ms.mamba_scan_ref(delta, x, b_ssm, c_ssm, a)
