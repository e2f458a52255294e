"""A rehearsal of the pipeline driver on four virtual CPU devices, at small
widths: set-up, a short window and the check, through the same code a chip
run takes.  The driver has no plain reference yet, so its check must read
``correct`` false.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_pipeline.py
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

REHEARSAL = """
import json, sys
sys.path[:0] = ["src", "."]
import jax
from bench import run
from bench.lib import compare
assert jax.device_count() == 4, jax.devices()
config = run.load_json("bench/configs/glm4-9b-pipe4.json")
config["model"].update(hidden_size=256, intermediate_size=512,
                       num_attention_heads=4, num_key_value_heads=2,
                       head_dim=64, num_hidden_layers=4, vocab_size=1024)
traffic = run.load_json("bench/traffic/pipe4_1f1b.json")
traffic.update(seq_len=128)
driver = run.load_module("bench/drivers/pipeline.py", "bench_driver_pipeline")
cell = driver.build({"config": config, "config_name": config["name"],
                     "traffic": traffic, "seed": 2**31 + 5, "chips": 4})
cell.setup()
result = cell.window(2.0)
context = cell.context()
cell.release()
print(json.dumps({"result": result, "first_loss": cell.first_loss,
                  "flops_per_token": context["flops_per_token"],
                  "correct": compare.passed(cell.check({}))}))
"""


def test_driver_runs_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", REHEARSAL], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["result"]["attempted"] >= 1, out
    assert out["result"]["metrics"]["pipeline_tokens_per_s"] > 0, out
    assert 6.0 < out["first_loss"] < 8.0, out    # about log(1024) at start
    assert not out["correct"], out
