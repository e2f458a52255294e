"""Guards that keep the TPU honest: ``chip_smoke.py`` refuses to report
without a chip, the compile cache goes where it should, and process fleets
refuse to fight their parent for the chip."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from conftest import SRC

ROOT = os.path.dirname(SRC)
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run_smoke(args: list, tmp_path, **env) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"), **env)
    return subprocess.run([sys.executable, *args], cwd=ROOT,
                          capture_output=True, text=True, env=env,
                          timeout=600)


def _ok_line(stdout: str) -> bool:
    return any('"ok": true' in line for line in stdout.splitlines())


def test_chip_smoke_refuses_without_tpu(tmp_path):
    proc = _run_smoke([SMOKE], tmp_path)
    assert proc.returncode != 0
    assert not _ok_line(proc.stdout)
    assert proc.stdout == ""              # refused before any work
    assert "no TPU" in proc.stderr


def test_chip_smoke_refuses_outside_the_repo(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the repo
    cannot pass either (here it refuses at the platform check first)."""
    lone = tmp_path / "lone"
    lone.mkdir()
    (lone / "chip_smoke.py").write_text(open(SMOKE).read())
    proc = _run_smoke([str(lone / "chip_smoke.py")], tmp_path)
    assert proc.returncode != 0 and not _ok_line(proc.stdout)


def test_chip_smoke_tiny_rehearsal_passes_phases_then_refuses(tmp_path):
    """The whole script at smoke widths on the CPU, kernel bodies in
    interpret mode: every phase passes, and the final platform check
    still refuses, because no TPU is present."""
    proc = _run_smoke(
        ["-c", "import sys, chip_smoke; "
               "sys.exit(chip_smoke.main([], sizes=chip_smoke.TINY))"],
        tmp_path, REPRO_FORCE_PALLAS_INTERPRET="1")
    assert proc.returncode != 0, proc.stderr[-3000:]
    assert not _ok_line(proc.stdout)
    lines = [json.loads(line) for line in proc.stdout.splitlines()]
    phases = {r["phase"]: r for r in lines if "phase" in r}
    assert set(phases) == {"kernels", "swarm", "serve"}, proc.stderr[-3000:]
    assert all(r["status"] == "pass" for r in phases.values())
    swarm = phases["swarm"]
    assert [e["merged_stages"] for e in swarm["epochs"]] == [2, 2]
    assert all(np.isfinite(e["mean_loss"]) for e in swarm["epochs"])
    assert phases["serve"]["token_parity_with_oracle"] is True
    assert "not on a TPU" in proc.stderr


def test_chip_smoke_four_chip_rehearsal(tmp_path):
    """``--four-chips`` at smoke widths on 4 forced host devices: 1f1b
    matches gpipe step for step, every device holds the same weight
    bytes, and the platform check still refuses at the end."""
    proc = _run_smoke(
        ["-c", "import sys, chip_smoke; sys.exit(chip_smoke.main("
               "['--four-chips'], sizes=chip_smoke.TINY))"],
        tmp_path, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert proc.returncode != 0 and not _ok_line(proc.stdout)
    phase = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith('{"phase"')]
    assert len(phase) == 1 and phase[0]["status"] == "pass", \
        proc.stderr[-3000:]
    assert max(phase[0]["loss_gaps_1f1b_vs_gpipe"]) < 5e-6
    for run in phase[0]["runs"].values():
        assert len(set(run["param_bytes_per_chip"])) == 1


# ---------------------------------------------------------------------------
# compile cache
# ---------------------------------------------------------------------------


@pytest.fixture
def cache_config():
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_compile_cache_defers_to_env(monkeypatch, tmp_path, cache_config):
    from repro.launch.compile_cache import use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # set nothing


def test_compile_cache_uses_fixed_checkout_path(monkeypatch, cache_config):
    from repro.launch.compile_cache import use_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = use_compile_cache()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert use_compile_cache() == path                     # same every call
    assert ".jax_cache/" in open(os.path.join(ROOT, ".gitignore")).read()


# ---------------------------------------------------------------------------
# one chip, one process
# ---------------------------------------------------------------------------


def _spec(platform=None):
    from repro.api.config import SwarmConfig
    from repro.configs import get, smoke_variant
    from repro.configs.base import TrainConfig
    from repro.runtime.actor import ActorSpec
    return ActorSpec("miner", 0, 0, smoke_variant(get("llama3.2-1b")).model,
                     SwarmConfig(n_stages=1), TrainConfig(),
                     ("127.0.0.1", 1), platform=platform)


def test_actor_fleet_refuses_to_spawn_on_a_tpu_parent(monkeypatch):
    from repro.runtime.actor import ActorSupervisor
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sup = ActorSupervisor()
    with pytest.raises(RuntimeError, match="holds the TPU"):
        sup.spawn([_spec(), _spec(platform="cpu")])
    assert sup.procs == {}                 # refused before any child


def test_cpu_pinned_fleet_passes_the_chip_guard(monkeypatch):
    from repro.runtime.actor import chip_owner_error
    assert chip_owner_error([_spec()]) is None            # CPU parent
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert chip_owner_error([_spec(platform="cpu")]) is None
    assert "miner0" in chip_owner_error([_spec()])


def test_serve_actor_fleet_refuses_on_a_tpu_parent(monkeypatch):
    from repro.configs import get, smoke_variant
    from repro.launch.serve import serve_swarm
    from repro.runtime import stage_model as sm
    spec = sm.SwarmModelSpec(smoke_variant(get("llama3.2-1b")).model, 2)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="holds the TPU"):
        serve_swarm(spec, [], n_lanes=1, max_len=8, transport="actors")
