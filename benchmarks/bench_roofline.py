"""§Roofline summary bench: prints the (arch x shape x mesh) roofline table

from the dry-run results file if present (produced by
``python -m repro.launch.dryrun --all --out dryrun_all.json``); otherwise
computes two small cells live so ``-m benchmarks.run`` is self-contained.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

from benchmarks.common import emit

RESULTS = os.path.join(os.path.dirname(__file__), "..", "dryrun_all.json")


def _emit_record(r: dict) -> None:
    """One row per dry-run cell.  The dry-run compiles on forced CPU host
    devices, so every row says platform=cpu: its times are roofline
    estimates from compiled HLO, not measurements."""
    if r.get("status") == "skipped":
        emit(f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}", 0.0,
             f"SKIP:{r['reason'][:60]}")
        return
    if r.get("status") != "ok":
        emit(f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}", 0.0,
             f"ERROR:{r.get('error', '?')[:80]}")
        return
    mem = (r.get("memory_per_device") or {}).get("total_bytes", 0) / 2**30
    emit(f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}", 0.0,
         f"t_comp={r['t_compute']:.4f}s;t_mem={r['t_memory']:.4f}s;"
         f"t_coll={r['t_collective']:.4f}s;bound={r['bottleneck']};"
         f"useful={r['useful_fraction']:.2f};mem={mem:.1f}GiB;platform=cpu")


def run() -> None:
    if os.path.exists(RESULTS):
        with open(RESULTS) as f:
            records = json.load(f)
        for r in records:
            _emit_record(r)
        ok = sum(1 for r in records if r.get("status") == "ok")
        emit("roofline/summary", 0.0,
             f"{ok}_ok/{len(records)}_cells")
        return
    # fallback: two small cells computed in a subprocess (needs the 512
    # fake-device env, which must not leak into this process).  The fake
    # devices are CPU devices, so the child is pinned to the CPU.
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    for arch, shape in (("xlstm-125m", "train_4k"),
                        ("llama3.2-1b", "decode_32k")):
        with tempfile.TemporaryDirectory() as tmp:
            out = os.path.join(tmp, "cell.json")
            proc = subprocess.run(
                [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
                 "--shape", shape, "--out", out],
                capture_output=True, text=True, timeout=1800,
                env=dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu"))
            if proc.returncode == 0:
                with open(out) as f:
                    for r in json.load(f):
                        _emit_record(r)
            else:
                emit(f"roofline/{arch}/{shape}", 0.0, "ERROR:dryrun_failed")


if __name__ == "__main__":
    run()
