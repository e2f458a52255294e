"""Pipeline-engine benchmark -> BENCH_pipeline.json (tracked across PRs).

Runs the §4 hot path — ``repro.core.pipeline`` via ``launch/train.py
--strategy pipeline`` — over the schedule x wire-codec grid on a small
dense config, in subprocesses (the stage count needs
``--xla_force_host_platform_device_count`` set *before* jax initialises,
which an already-running bench harness cannot do).  The forced host
devices are CPU devices: every child runs with ``JAX_PLATFORMS=cpu``, and
every record says ``"platform": "cpu"`` — these rows time XLA's CPU
backend, never a chip.

The artifact records, per benchmark: us/step, final loss after the fixed
step budget, on-wire bytes per boundary hop (int8 scales accounted), the
timetable-measured bubble fraction and the peak activation-stash
estimate.  The derived block checks the PR acceptance claims:
  * int8 wire codes cut wire_bytes_per_hop >= 1.9x vs bf16 at matching loss
  * 1F1B shrinks the stash vs GPipe at n_micro >= 2 * n_stages, with both
    schedules agreeing on loss to tolerance
  * zerobubble/interleaved(V=2) land strictly below 1F1B's bubble
    (<= 0.14 / <= 0.158 at P=4, M=8) at matching loss
  * the int8 stash never exceeds the bf16 stash on the ring schedules
    (the rings hold the codes+scales pair, not decoded activations)

``BENCH_QUICK=1`` shrinks the grid/steps (smoke.sh schema validation).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

from benchmarks.common import emit

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
ARTIFACT = os.path.join(ROOT, "BENCH_pipeline.json")
QUICK_ARTIFACT = os.path.join(tempfile.gettempdir(),
                              "BENCH_pipeline.quick.json")


def artifact_path() -> str:
    """Quick runs validate a scratch artifact; full runs refresh the
    committed one."""
    return QUICK_ARTIFACT if os.environ.get("BENCH_QUICK", "0") == "1" \
        else ARTIFACT

SCHEMA_KEYS = {"schema", "arch", "config", "benchmarks", "derived"}
BENCH_KEYS = {"name", "platform", "schedule", "virtual_stages", "wire_codec",
              "us_per_step", "final_loss", "wire_bytes_per_hop",
              "bubble_fraction", "peak_stash_bytes", "stash_codes",
              "grad_ring_codes", "loop_length"}


def _scenario(name: str, schedule: str, codec: str, cfg: dict,
              virtual_stages: int = 1) -> dict:
    """One training run in a subprocess; returns the benchmark record."""
    with tempfile.NamedTemporaryFile(suffix=".jsonl", delete=False) as f:
        metrics_path = f.name
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count="
                  f"{cfg['n_stages']}",
        PYTHONPATH=os.path.join(ROOT, "src"),
    )
    cmd = [
        sys.executable, "-m", "repro.launch.train",
        "--arch", cfg["arch"], "--smoke", "--strategy", "pipeline",
        "--pipeline-schedule", schedule, "--wire-codec", codec,
        "--pipeline-stages", str(cfg["n_stages"]),
        "--pipeline-microbatches", str(cfg["n_microbatches"]),
        "--bottleneck-dim", str(cfg["bottleneck_dim"]),
        "--steps", str(cfg["steps"]), "--batch-size", str(cfg["batch"]),
        "--seq-len", str(cfg["seq"]), "--log-every", str(cfg["steps"]),
        "--lr", "0.1", "--metrics-out", metrics_path,
    ]
    if virtual_stages > 1:
        # interleaved needs layers divisible by stages * virtual stages
        cmd += ["--pipeline-virtual-stages", str(virtual_stages),
                "--n-layers", str(cfg["n_stages"] * virtual_stages)]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                              cwd=ROOT, timeout=1800)
        assert proc.returncode == 0, proc.stderr[-4000:]
        with open(metrics_path) as mf:
            records = [json.loads(line) for line in mf]
    finally:
        if os.path.exists(metrics_path):
            os.unlink(metrics_path)
    stats, final = records[0], records[-1]
    return {
        "name": name,
        "platform": "cpu",
        "schedule": schedule,
        "virtual_stages": stats.get("virtual_stages", 1),
        "wire_codec": codec,
        "us_per_step": final["us_per_step"],
        "final_loss": round(final["loss"], 6),
        "wire_bytes_per_hop": stats["wire_bytes_per_hop"],
        # timetable-measured idle fraction (schedule_stats derives it from
        # the compiled Timetable, not the closed form)
        "bubble_fraction": round(stats["bubble_fraction"], 4),
        "peak_stash_bytes": stats["stash_bytes"],
        "stash_codes": stats["stash_codes"],
        "grad_ring_codes": stats.get("grad_ring_codes", 0),
        "loop_length": stats["loop_length"],
    }


def run() -> None:
    quick = os.environ.get("BENCH_QUICK", "0") == "1"
    cfg = {
        "arch": "llama3.2-1b",
        "n_stages": 2 if quick else 4,
        "n_microbatches": 4 if quick else 8,   # >= 2 * n_stages
        "batch": 4 if quick else 8,
        "seq": 16 if quick else 32,
        "steps": 6 if quick else 40,
        "bottleneck_dim": 16,
    }
    grid = [
        ("gpipe_bf16", "gpipe", "none", 1),
        ("gpipe_int8", "gpipe", "int8", 1),
        ("1f1b_bf16", "1f1b", "none", 1),
        ("1f1b_int8", "1f1b", "int8", 1),
        ("zerobubble_bf16", "zerobubble", "none", 1),
        ("zerobubble_int8", "zerobubble", "int8", 1),
        # V=2 doubles the layer count (8 layers as 4 stages x 2 chunks),
        # so us_per_step is not comparable to the 4-layer rows; the bubble
        # and stash columns are the point
        ("interleaved_v2_bf16", "interleaved", "none", 2),
        ("interleaved_v2_int8", "interleaved", "int8", 2),
    ]
    if quick:
        grid = [("gpipe_bf16", "gpipe", "none", 1),
                ("1f1b_int8", "1f1b", "int8", 1),
                ("zerobubble_bf16", "zerobubble", "none", 1),
                ("interleaved_v2_bf16", "interleaved", "none", 2)]

    benches = []
    for name, schedule, codec, v in grid:
        rec = _scenario(name, schedule, codec, cfg, virtual_stages=v)
        benches.append(rec)
        emit(f"pipeline/{name}", rec["us_per_step"],
             f"loss={rec['final_loss']};bytes_hop={rec['wire_bytes_per_hop']};"
             f"stash={rec['peak_stash_bytes']};"
             f"bubble={rec['bubble_fraction']}")

    by = {r["name"]: r for r in benches}

    def gap(a, b):
        return abs(a - b) / max(abs(a), abs(b), 1e-9)

    derived = {}
    if "gpipe_int8" in by:
        derived["int8_wire_cut_x"] = round(
            by["gpipe_bf16"]["wire_bytes_per_hop"]
            / by["gpipe_int8"]["wire_bytes_per_hop"], 3)
        derived["loss_gap_int8_vs_bf16"] = round(
            gap(by["gpipe_int8"]["final_loss"],
                by["gpipe_bf16"]["final_loss"]), 6)
    if "1f1b_bf16" in by:
        derived["stash_cut_1f1b_x"] = round(
            by["gpipe_bf16"]["peak_stash_bytes"]
            / by["1f1b_bf16"]["peak_stash_bytes"], 3)
        derived["loss_gap_1f1b_vs_gpipe"] = round(
            gap(by["1f1b_bf16"]["final_loss"],
                by["gpipe_bf16"]["final_loss"]), 6)
        derived["acceptance"] = {
            "int8_cut_ge_1p9x": derived.get("int8_wire_cut_x", 0) >= 1.9,
            "int8_loss_match_1pct": derived.get(
                "loss_gap_int8_vs_bf16", 1) < 0.01,
            "1f1b_stash_smaller_at_2x_micro": (
                cfg["n_microbatches"] >= 2 * cfg["n_stages"]
                and by["1f1b_bf16"]["peak_stash_bytes"]
                < by["gpipe_bf16"]["peak_stash_bytes"]),
            "1f1b_loss_match_1pct": derived["loss_gap_1f1b_vs_gpipe"] < 0.01,
        }
    # ISSUE 9 acceptance: the new schedules' timetable-measured bubbles
    # land strictly below 1F1B's, and the int8 ring stash regression
    # (codes stashed alongside decoded bf16) stays fixed
    acc = derived.setdefault("acceptance", {})
    base_bubble = by["1f1b_bf16"]["bubble_fraction"] if "1f1b_bf16" in by \
        else (cfg["n_stages"] - 1) / (cfg["n_microbatches"]
                                      + cfg["n_stages"] - 1)
    if "zerobubble_bf16" in by:
        zb = by["zerobubble_bf16"]
        acc["zerobubble_bubble_le_0p14"] = zb["bubble_fraction"] <= 0.14
        acc["zerobubble_beats_1f1b"] = zb["bubble_fraction"] < base_bubble
        derived["loss_gap_zerobubble_vs_gpipe"] = round(
            gap(zb["final_loss"], by["gpipe_bf16"]["final_loss"]), 6)
        acc["zerobubble_loss_match_1pct"] = \
            derived["loss_gap_zerobubble_vs_gpipe"] < 0.01
    if "interleaved_v2_bf16" in by:
        il = by["interleaved_v2_bf16"]
        acc["interleaved_bubble_le_0p158"] = il["bubble_fraction"] <= 0.158
        acc["interleaved_beats_1f1b"] = il["bubble_fraction"] < base_bubble
    for sched in ("1f1b", "zerobubble", "interleaved_v2"):
        b16, i8 = f"{sched}_bf16", f"{sched}_int8"
        if b16 in by and i8 in by:
            acc[f"{sched}_int8_stash_not_larger"] = (
                by[i8]["peak_stash_bytes"] <= by[b16]["peak_stash_bytes"])

    artifact = {
        "schema": "bench_pipeline/v2",
        "arch": f"{cfg['arch']} (smoke)",
        "config": {k: v for k, v in cfg.items() if k != "arch"},
        "quick": quick,
        "benchmarks": benches,
        "derived": derived,
    }
    out = artifact_path()
    with open(out, "w") as f:
        json.dump(artifact, f, indent=1)
        f.write("\n")
    emit("pipeline/artifact", 0.0, out)
    validate_artifact(out)


def validate_artifact(path: str | None = None) -> dict:
    """Schema gate used by `benchmarks/run.py --quick` and scripts/smoke.sh."""
    with open(path or artifact_path()) as f:
        art = json.load(f)
    missing = SCHEMA_KEYS - set(art)
    assert not missing, f"BENCH_pipeline.json missing keys: {missing}"
    assert art["schema"] == "bench_pipeline/v2", art["schema"]
    assert art["benchmarks"], "no benchmark records"
    for rec in art["benchmarks"]:
        miss = BENCH_KEYS - set(rec)
        assert not miss, f"benchmark {rec.get('name')} missing {miss}"
        assert rec["platform"] == "cpu", rec["platform"]
    return art


if __name__ == "__main__":
    run()
