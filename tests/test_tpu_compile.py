"""Ahead-of-time compiles of the main-path Pallas kernels for TPU v5e.

Interpret mode (tests/test_kernels.py) checks what a kernel computes; only
the TPU compiler checks that it can run on the chip: block shapes against
the (8, 128) tiling, 1-D blocks against XLA's layouts, operations Mosaic
lowers.  Each test compiles one kernel at the shapes of ``chip_smoke.py``'s
phases (the paper's iota-bottleneck-1.5b: d_model 2048, 32 q / 8 kv heads,
head_dim 64, a 32-wide bottleneck, one stage's ~94M-element weight vector)
for one chip of a described ``v5e:2x2`` topology, with no chip attached,
and asserts that the program holds the kernel (``tpu_custom_call``).  The
kernels of the swarm's main path carry a stable name (``pallas_call``'s
``name=``), which the compiled program's custom call bears, so that a
device trace names them whatever the code around them is called.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (
    bottleneck_fused as bf,
    decode_attention as da,
    flash_attention as fa,
    quant_stream as qs,
    shard_merge as sm,
)

B, S, H, KH, HD, D, DB = 4, 1024, 32, 8, 64, 2048, 32
PROMPT, MAX_LEN = 512, 512 + 32          # serve: 512-token prompt, 32 new
STAGE_VECTOR = 94_443_520                # one swarm stage, flattened f32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    saved_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure: no compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache out
        saved_cache = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", saved_cache)
    finally:
        if saved_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)


_COMPILED: dict = {}


def _compiled_text(name, sharding) -> str:
    """HLO text of case ``name`` compiled for the described chip, once
    per process."""
    if name not in _COMPILED:
        fn, *shapes = CASES[name]
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
                for shape, dtype in shapes]
        _COMPILED[name] = jax.jit(fn).lower(*args).compile().as_text()
    return _COMPILED[name]


def _kernel_lines(text: str) -> list:
    return [ln for ln in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln]


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32

CASES = {
    "flash_attention": (
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
        ((B, S, H, HD), BF16), ((B, S, KH, HD), BF16), ((B, S, KH, HD), BF16)),
    "decode_attention_decode": (
        lambda q, k, v, n: da.decode_attention(q, k, v, q_offset=n - 1,
                                               kv_len=n),
        ((B, 1, H, HD), BF16), ((B, MAX_LEN, KH, HD), BF16),
        ((B, MAX_LEN, KH, HD), BF16), ((B,), I32)),
    "decode_attention_prefill": (
        lambda q, k, v, n: da.decode_attention(q, k, v, q_offset=0,
                                               kv_len=n),
        ((1, PROMPT, H, HD), BF16), ((1, MAX_LEN, KH, HD), BF16),
        ((1, MAX_LEN, KH, HD), BF16), ((1,), I32)),
    "quantize_int8_stage_vector": (
        lambda x: qs.quantize_int8(x), ((STAGE_VECTOR,), F32)),
    "dequantize_int8_stage_vector": (
        lambda q, s: qs.dequantize_int8(q, s),
        ((STAGE_VECTOR,), jnp.int8), ((STAGE_VECTOR // qs.BLOCK,), F32)),
    "quantize_wire": (
        lambda z: qs.quantize_wire(z)[:2], ((B, S, DB), F32)),
    "dequantize_wire": (
        lambda q, s: qs.dequantize_wire(q, s, qs.BLOCK),
        ((B, S, DB), jnp.int8), ((B * S * DB // qs.BLOCK,), F32)),
    "shard_merge_m2": (
        lambda x, v: sm.shard_merge(x, v),
        ((2, STAGE_VECTOR), F32), ((2,), jnp.bool_)),
    "shard_merge_m3": (
        lambda x, v: sm.shard_merge(x, v),
        ((3, 31_481_173), F32), ((3,), jnp.bool_)),
    "shard_merge_m16": (
        lambda x, v: sm.shard_merge(x, v),
        ((16, 787_029), F32), ((16,), jnp.bool_)),
    "bottleneck_encode": (
        lambda x, g, w: bf.bottleneck_encode(x, g, w),
        ((B, S, D), BF16), ((D,), F32), ((D, DB), F32)),
    "bottleneck_decode_gated": (
        lambda z, w, a: bf.bottleneck_decode_gated(z, w, a),
        ((B, S, DB), BF16), ((DB, D), F32), ((), F32)),
}


# the stable name each main-path case's kernel carries
KERNEL_NAMES = {
    "flash_attention": "flash_attention_fwd",
    "quantize_int8_stage_vector": "quantize_int8",
    "dequantize_int8_stage_vector": "dequantize_int8",
    "quantize_wire": "quantize_int8",
    "dequantize_wire": "dequantize_int8",
    "shard_merge_m2": "shard_merge",
    "shard_merge_m3": "shard_merge",
    "shard_merge_m16": "shard_merge",
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    assert "tpu_custom_call" in _compiled_text(name, one_chip)


@pytest.mark.parametrize("name", sorted(KERNEL_NAMES))
def test_kernel_carries_its_name(name, one_chip):
    """The custom call is named after the kernel (``%<name>.<n> = ...``)."""
    lines = _kernel_lines(_compiled_text(name, one_chip))
    assert lines
    assert all(ln.lstrip().removeprefix("ROOT ").startswith(
        "%" + KERNEL_NAMES[name] + ".") for ln in lines), lines


def test_named_flash_kernel_is_found_as_the_roofline_reader_looks(one_chip):
    """``bench/metrics/flash_attention_roofline.swarm.py`` finds the flash
    forward as an op whose text holds ``closed_call`` or
    ``tpu_custom_call`` and its head-major output bf16[B, H, S, D]."""
    out = "= bf16[{},{},{},{}]".format(B, H, S, HD)
    found = [ln for ln in _kernel_lines(_compiled_text("flash_attention",
                                                        one_chip))
             if ("closed_call" in ln or "tpu_custom_call" in ln)
             and out in ln]
    assert len(found) == 1 and "flash_attention_fwd" in found[0]


def test_named_quantize_kernel_is_found_as_the_roofline_reader_looks(
        one_chip):
    """``bench/metrics/quantize_int8_roofline.swarm.py`` finds the int8
    quantize as a ``tpu_custom_call`` op whose output tuple starts
    ``= (s8[rows, 128]``, and reads the vector's length from it."""
    codes = re.compile(r"= \(s8\[(\d+),(\d+)\]")
    found = [ln for ln in _kernel_lines(_compiled_text(
        "quantize_int8_stage_vector", one_chip)) if codes.search(ln)]
    assert len(found) == 1 and "quantize_int8" in found[0]
    rows, width = codes.search(found[0]).groups()
    assert int(rows) * int(width) == STAGE_VECTOR
