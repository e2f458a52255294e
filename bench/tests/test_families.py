"""A model family is new files only.

(a) The swarm cells' plain reference, whose model code lives in its
family's module (``bench/models/dense_decoder.py``), gives, bit for bit,
the numbers recorded in ``data/swarm_reference_parent.json`` from the
reference as it was before the family split (one block in
``bench/lib/reference.py``): the losses and the grad1, change3 and anchor
norms at the small test size, and the FLOP count and attention shape that
the per-layer readers get at the configurations' own sizes (the pipeline
cell's as the old formula gave them).

(b) A family kept beside the tests (``qk_norm_decoder.py``) runs a whole
swarm cell, program and reference, and passes its check, with no file
outside ``bench/tests/`` touched.

(c) A family whose layers differ by their model-wide index
(``positional_decoder.py``) gets the same loss from the pipeline cell's
stage-by-stage reference, on four virtual CPU devices, as from the
whole-model reference: each layer reaches ``blocks`` with its own index.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_families.py
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "swarm_reference_parent.json")


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED) as f:
        return json.load(f)


def build(workload: str, seed: int, small: bool):
    from bench import run
    from bench.tests.small import shrink
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    files = run.cell_files(bench, workload)
    if small:
        shrink(files)
    driver = run.load_module(os.path.join(
        ROOT, "bench", "drivers", files["traffic"]["driver"] + ".py"),
        "bench_driver_" + files["traffic"]["driver"])
    return driver.build(dict(files, seed=seed, chips=files["cell"]["chips"]))


@pytest.mark.parametrize("workload", ["swarm-epoch", "swarm-train"])
def test_swarm_reference_is_unmoved(recorded, workload):
    want = recorded["cells"][workload]
    cell = build(workload, recorded["seed"], small=True)
    cell.setup()
    cell.release()
    assert [list(r) for r in cell.routing] == want["routing"]
    assert cell.reference("f32", cell.routing) == want["reference"]
    if "control" in want:
        assert cell.reference("fp8", cell.routing) == want["control"]


@pytest.mark.parametrize("workload", ["swarm-epoch", "swarm-train",
                                      "pipe4-1f1b"])
def test_readers_get_the_same_shapes_and_flops(recorded, workload):
    """What ``context()`` gives the per-layer readers at the cell's own
    sizes: pure arithmetic on the configuration, no program run."""
    cell = build(workload, recorded["seed"], small=False)
    cell.readings, cell.window_start_ns, cell.vector_len = {}, 0, []
    ctx = cell.context()
    want = recorded["real"][workload]
    assert ctx["flops_per_token"] == want["flops_per_token"]
    assert ctx["attention"] == want["attention"]


def test_family_outside_bench_models_passes_its_check():
    from bench import run
    from bench.tests.small import shrink

    def qk_norm(files):
        shrink(files)
        files["config"]["family"] = "bench.tests.qk_norm_decoder"

    out = run.main(["--workload", "swarm-train", "--seed", str(2**31 + 91),
                    "--seconds", "2"], require_chip=False, override=qk_norm)
    assert out["correct"], out["check"]


def test_family_block_is_not_the_dense_one():
    """The test family's block computes something else than the dense
    one's on the same weights, so (b) exercises a different reference."""
    import jax
    import jax.numpy as jnp
    from bench.lib.reference import leaf_name
    from bench.models import dense_decoder
    from bench.tests import qk_norm_decoder
    from bench.tests.small import SMALL_MODEL
    m = dict(SMALL_MODEL, rope_theta=10000.0, norm_eps=1e-5)
    d, H, KH, D, F = (m["hidden_size"], m["num_attention_heads"],
                      m["num_key_value_heads"], m["head_dim"],
                      m["intermediate_size"])
    shapes = {"attn": {"wq": (1, d, H * D), "wk": (1, d, KH * D),
                       "wv": (1, d, KH * D), "wo": (1, H * D, d),
                       "q_norm": (1, D), "k_norm": (1, D)},
              "attn_norm": (1, d), "ffn_norm": (1, d),
              "mlp": {"w_gate": (1, d, F), "w_up": (1, d, F),
                      "w_out": (1, F, d)}}
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    key = jax.random.key(0)
    pb = jax.tree_util.tree_unflatten(tree, [
        dense_decoder.init_leaf(jax.random.fold_in(key, i), leaf_name(p),
                                shp, jnp.float32)
        for i, (p, shp) in enumerate(flat)])
    x = jax.random.normal(jax.random.fold_in(key, 99), (1, 16, d))
    a = dense_decoder.blocks(pb, x, m, "f32", 0)
    b = qk_norm_decoder.blocks(pb, x, m, "f32", 0)
    assert float(jnp.max(jnp.abs(a - b))) > 1e-2


POSITIONAL = """
import json, sys
sys.path[:0] = ["src", "."]
import jax
import numpy as np
from bench import models, run
from bench.drivers import pipeline as drv
from bench.lib import reference as ref
assert jax.device_count() == 4, jax.devices()
files = run.cell_files(run.load_json("BENCHMARK.json"), "pipe4-1f1b")
m = files["config"]["model"]
m.update(hidden_size=256, intermediate_size=512, num_attention_heads=4,
         num_key_value_heads=2, head_dim=64, num_hidden_layers=8,
         vocab_size=1024)
files["config"]["objective"]["z_loss"] = 0.0
files["config"]["family"] = "bench.tests.positional_decoder"
files["traffic"].update(seq_len=64)
cell = drv.build(dict(files, seed=2**31 + 13, chips=4))
cell.setup()
cell.release()
staged = cell.reference("f32")["losses"][0]
w = {k: np.asarray(jax.device_get(a))
     for k, a in drv.flat(cell.make(cell.seed)[0]).items()}
stages = [drv.reference_tree({
    k: w[k][s:s + 1] if k.startswith(drv.STAGED) else w[k]
    for k in drv.stage_names(w, s, 4)}) for s in range(4)]
b = cell.batches().batch(0)
whole = {f: float(ref.loss_fn(stages, b["tokens"], b["labels"], m, "f32", f))
         for f in ("bench.tests.positional_decoder", "dense_decoder")}
print(json.dumps({"staged": staged, **whole}))
"""


def test_pipeline_reference_hands_each_layer_its_index():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", POSITIONAL], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["staged"] == pytest.approx(
        got["bench.tests.positional_decoder"], rel=1e-5), got
    assert abs(got["dense_decoder"] - got["staged"]) > 1e-3, got
