"""Compile the swarm cells' stage programs for one chip of a described TPU
v5e, and the pipeline driver's step for its 2x2 host, at the widths of the
configuration files, without a chip.

The chip's compiler refuses here what it would refuse there (tiling, fast
memory, a program that does not fit), at no chip time.  Run:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_aot_v5e.py
"""
from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    try:
        described = topologies.get_topology_desc(platform="tpu",
                                                 topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    return described


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_kernels(monkeypatch):
    """The dispatch asks the backend, which is the CPU here: take the
    chip's branch, the compiled Pallas kernels."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


@pytest.mark.parametrize("traffic", ["swarm_train_only"])
def test_stage_programs_compile_for_v5e(one_chip, traffic, chip_kernels):
    import jax
    import jax.numpy as jnp
    from bench import models
    from repro.runtime import stage_model as sm

    cfg = load("bench/configs/stablelm-3b-swarm.json")
    t = load(f"bench/traffic/{traffic}.json")
    family = models.get(cfg["family"])
    spec = sm.SwarmModelSpec(family.program_config(cfg["name"], cfg["model"]),
                             t["n_stages"], True, t["bottleneck_dim"])
    B, S, db = t["batch_size"], t["seq_len"], t["bottleneck_dim"]

    def shapes(stage):
        tree = jax.eval_shape(
            lambda k: sm.init_stage_params(k, spec, stage), jax.random.key(0))
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    first, last = shapes(0), shapes(t["n_stages"] - 1)
    tokens = arg((B, S), jnp.int32)
    code = arg((B, S, db), jnp.bfloat16)
    programs = {
        "forward/first": sm.stage_forward.lower(first, tokens, spec=spec,
                                                role="first"),
        "backward/first": sm.stage_backward.lower(first, tokens, code,
                                                  spec=spec, role="first"),
        "loss_and_grads/last": sm.last_stage_loss_and_grads.lower(
            last, code, tokens, spec=spec),
    }
    for name, lowered in programs.items():
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        temp = getattr(mem, "temp_size_in_bytes", 0)
        assert temp < 12 * 2**30, (name, temp)
        if name.startswith("forward"):
            assert "tpu_custom_call" in compiled.as_text(), name


def compile_pipeline_step(topo, **change):
    """The pipeline driver's step at ``glm4-9b-pipe4``'s sizes, with the
    model keys ``change`` sets, compiled for the 2x2 host."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    from bench import models
    from bench.drivers import pipeline as drv

    cfg = load("bench/configs/glm4-9b-pipe4.json")
    t = load("bench/traffic/pipe4_1f1b.json")
    mesh = drv.make_mesh(topo.devices, t)
    shapes, shardings, step = drv.build_step(
        models.get(cfg["family"]), cfg["name"], dict(cfg["model"], **change),
        t, mesh, cfg["objective"]["z_loss"])
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        shapes, shardings)
    rows = jax.ShapeDtypeStruct((t["batch_size"], t["seq_len"]), jnp.int32,
                                sharding=NamedSharding(mesh, PartitionSpec()))
    return step.lower(params, {"tokens": rows, "labels": rows}).compile()


def test_pipeline_step_fits_v5e_2x2(topo, chip_kernels):
    """The pipeline driver's whole step (1f1b over four stages, flash
    kernel and fused boundary codecs, the hand-offs between chips) at the
    widths of ``glm4-9b-pipe4``: the compiler that refuses a step that does
    not fit a chip's memory accepts this one."""
    text = compile_pipeline_step(topo).as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text


@pytest.mark.parametrize("change", [
    {"num_hidden_layers": 16},                         # 4 layers a stage
    {"num_hidden_layers": 4, "vocab_size": 151552},    # published vocabulary
], ids=["one_more_layer_a_stage", "published_vocabulary_at_one_layer"])
def test_pipeline_cut_is_what_the_chips_force(topo, chip_kernels, change):
    """``glm4-9b-pipe4``'s cut: one more layer a stage, or the published
    vocabulary at even one layer a stage, does not fit a chip."""
    import jax
    with pytest.raises(jax.errors.JaxRuntimeError,
                       match="Ran out of memory in memory space hbm"):
        compile_pipeline_step(topo, **change)
