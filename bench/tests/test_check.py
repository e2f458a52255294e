"""The comparison that decides ``correct``, driven end to end on the CPU.

Each test runs a whole cell (set-up, a short window, the reference and the
comparison) at the small sizes of ``bench/tests/small.py``, without the
harness's look for a chip.  A sound run must come out correct.  A run with
the timed path broken underneath must not, once for each fault a swarm cell
can have:

  unchanged   every optimizer step returns the parameters it was given
  half_batch  the last stage's loss and gradients over half of the batch,
              the mean taken over that half
  answer      the loss a tick reports altered where it is produced
  no_merge    (merging timelines) the outer step and anchor download left
              out
  accept_all  (validating timelines) the validator passes every item
  reject_all  (validating timelines) the validator rejects every item

The exchange between chips does not exist in these one-chip cells.  The
control, the reference in fp8 put in the program's place, is run at the
cells' own sizes on the chip by ``bench/calibrate.py``; here it is run at
the small sizes, where it must fail the cell's limits.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_check.py
"""
from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

SEED = 2**31 + 77       # above 32 signed bits, as the driver's seeds are


def run_cell(workload: str, seed: int = SEED) -> dict:
    from bench import run
    from bench.tests.small import shrink
    return run.main(["--workload", workload, "--seed", str(seed),
                     "--seconds", "2"], require_chip=False, override=shrink)


def plant(monkeypatch, fault: str) -> None:
    import jax
    import jax.numpy as jnp
    from repro.api import phases
    from repro.runtime import stage_model as sm
    from repro.runtime.miner import Miner

    if fault == "unchanged":
        real = Miner._apply

        def apply(self, grads):
            params = self.params
            real(self, grads)
            self.params = params
        monkeypatch.setattr(Miner, "_apply", apply)
    elif fault == "half_batch":
        def backward_last(self, sample_key, labels):
            z_in = self._pending.pop(sample_key)
            h = z_in.shape[0] // 2
            loss, g_params, g_half = sm.last_stage_loss_and_grads(
                self.params, z_in[:h], labels[:h], self.spec)
            self._apply(g_params)
            g_z = jnp.concatenate([g_half, jnp.zeros_like(g_half)])
            return float(loss), g_z
        monkeypatch.setattr(Miner, "backward_last", backward_last)
    elif fault == "answer":
        real = Miner.backward_last

        def backward_last(self, sample_key, labels):
            loss, g = real(self, sample_key, labels)
            return loss * 1.01, g
        monkeypatch.setattr(Miner, "backward_last", backward_last)
    elif fault == "no_merge":
        monkeypatch.setattr(phases.SyncPhase, "_outer_step_and_full_sync",
                            lambda self, swarm, state, s, merged: None)
    elif fault in ("accept_all", "reject_all"):
        from repro.runtime import validator
        monkeypatch.setattr(validator, "COSINE_THRESHOLD",
                            -2.0 if fault == "accept_all" else 2.0)
    else:
        raise ValueError(fault)
    del jax


@pytest.mark.parametrize("workload", ["swarm-epoch", "swarm-train"])
def test_sound_run_is_correct(workload):
    out = run_cell(workload)
    assert out["correct"], out["check"]
    assert out["compiles"]["window"]["misses"] == 0, out["compiles"]


@pytest.mark.parametrize("workload,fault", [
    ("swarm-train", "unchanged"),
    ("swarm-train", "half_batch"),
    ("swarm-train", "answer"),
    ("swarm-epoch", "no_merge"),
    ("swarm-epoch", "accept_all"),
    ("swarm-epoch", "reject_all"),
])
def test_fault_is_not_correct(monkeypatch, workload, fault):
    plant(monkeypatch, fault)
    out = run_cell(workload)
    assert not out["correct"], (fault, out["check"])


@pytest.mark.parametrize("workload", ["swarm-epoch", "swarm-train"])
def test_control_is_not_correct(workload):
    """The control, the reference in fp8 put in the program's place, fails
    the cell's limits where the program on the same seed passes them.  The
    reference has no validator, so the control is held to the limits of the
    numbers it gives."""
    from bench import calibrate, run
    from bench.lib import compare
    from bench.tests.small import shrink
    rows = calibrate.main(["--workload", workload, "--seeds", str(SEED),
                           "--control-seeds", str(SEED)],
                          require_chip=False, override=shrink)
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    limits = run.cell_files(bench, workload)["limits"]
    got = {r["kind"]: compare.passed(compare.rows(
        r["numbers"], {k: v for k, v in limits.items() if k in r["numbers"]}))
        for r in rows}
    assert got == {"program": True, "control": False}, rows


def test_cache_entry_without_access_time_is_dropped(tmp_path, monkeypatch):
    """JAX fails every write to its cache while one entry lacks its
    access-time file; the harness drops such entries before it starts."""
    from bench import run
    monkeypatch.setattr(run, "CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    for name in ("stale-cache", "kept-cache", "kept-atime"):
        (tmp_path / name).write_bytes(b"x")
    run.use_cache_dir()
    assert sorted(p.name for p in tmp_path.iterdir()) == ["kept-atime",
                                                          "kept-cache"]
