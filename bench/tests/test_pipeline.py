"""The pipeline cell, ``pipe4-1f1b``, driven end to end on four virtual CPU
devices at small widths: set-up, a short window and the check against the
plain reference, through ``bench/run.py`` without its look for a chip.  A
sound run must come out correct.  A run with the timed path broken
underneath must not, once for each fault the cell can have:

  half_batch  the step's loss and gradients over the first half of the
              batch, the mean taken over that half
  drain       the last microbatch left out, the mean over the other seven:
              what a 1F1B timetable that drops its drain would compute
  answer      the loss the step reports altered where it is produced
  unchanged   every step returns the parameters it was given
  exchange    the hand-offs between chips left out: every ``ppermute``
              delivers zeros

The control and the faults planted in the reference put in the program's
place (what ``bench/calibrate.py`` reads on the chip at the cell's size)
must fail the cell's limits too.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_pipeline.py
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

REHEARSAL = """
import dataclasses, json, sys
sys.path[:0] = ["src", "."]
import jax
import jax.numpy as jnp
from repro.core import pipeline as pl
from bench import run
assert jax.device_count() == 4, jax.devices()
fault = sys.argv[1]
real = pl.pipeline_loss_and_grads


def planted(params, batch, cfg, spec, mesh, **kw):
    rows, m = batch["tokens"].shape[0], spec.n_microbatches
    keep = {"half_batch": (rows // 2, m // 2),
            "drain": (rows - rows // m, m - 1)}.get(fault)
    if keep:
        batch = {k: v[: keep[0]] for k, v in batch.items()}
        spec = dataclasses.replace(spec, n_microbatches=keep[1])
    loss, grads = real(params, batch, cfg, spec, mesh, **kw)
    if fault == "answer":
        loss = loss * 1.01
    if fault == "unchanged":
        grads = jax.tree.map(jnp.zeros_like, grads)
    return loss, grads


if fault == "exchange":
    jax.lax.ppermute = lambda x, axis_name, perm: jnp.zeros_like(x)
elif fault != "none":
    pl.pipeline_loss_and_grads = planted


def small(files):
    files["config"]["model"].update(
        hidden_size=256, intermediate_size=512, num_attention_heads=4,
        num_key_value_heads=2, head_dim=64, num_hidden_layers=4,
        vocab_size=1024)
    files["traffic"].update(seq_len=128)


out = run.main(["--workload", "pipe4-1f1b", "--seed", str(2**31 + 5),
                "--seconds", "2"], require_chip=False, override=small)
print(json.dumps(out))
"""


CALIBRATION = """
import json, sys
sys.path[:0] = ["src", "."]
from bench import calibrate, run
from bench.lib import compare
limits = run.load_json("bench/workloads/pipe4-1f1b.json")["limits"]


def small(files):
    files["config"]["model"].update(
        hidden_size=256, intermediate_size=512, num_attention_heads=4,
        num_key_value_heads=2, head_dim=64, num_hidden_layers=4,
        vocab_size=1024)
    files["traffic"].update(seq_len=128)


seed = str(2**31 + 7)
rows = calibrate.main(["--workload", "pipe4-1f1b", "--seeds", seed,
                       "--control-seeds", seed, "--fault-seeds", seed],
                      require_chip=False, override=small)
print(json.dumps({r["kind"]: compare.passed(compare.rows(r["numbers"], limits))
                  for r in rows}))
"""


def rehearse(fault: str, script: str = REHEARSAL) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", script, fault], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sound_run_on_four_virtual_devices_is_correct():
    out = rehearse("none")
    assert out["attempted"] >= 1 and out["failed"] == 0, out
    assert out["metrics"]["pipeline_tokens_per_s"]["value"] > 0, out
    assert out["compiles"]["window"]["misses"] == 0, out["compiles"]
    assert out["device"]["count"] == 4, out["device"]
    assert out["correct"], out["check"]


@pytest.mark.parametrize("fault", ["half_batch", "drain", "answer",
                                   "unchanged", "exchange"])
def test_fault_is_not_correct(fault):
    out = rehearse(fault)
    assert not out["correct"], (fault, out["check"])


def test_control_and_reference_faults_are_not_correct():
    """The control, the reference in fp8 put in the program's place, and
    the faults planted in the reference (``cell.faults``: the half batch,
    the drain left out, the hand-offs delivering zeros) fail the cell's limits where the program on the
    same seed passes them, as ``bench/calibrate.py`` reads them on the
    chip."""
    got = rehearse("none", CALIBRATION)
    assert got == {"program": True, "control": False, "half_batch": False,
                   "drain": False, "exchange": False}, got
