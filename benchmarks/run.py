"""Benchmark harness entry point: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).
Modules may additionally write machine-readable artifacts (tracked across
PRs): ``bench_pipeline`` writes ``BENCH_pipeline.json`` and
``bench_butterfly`` writes ``BENCH_butterfly.json`` at the repo root.

  fig5   bench_convergence        — bottleneck compression vs baseline
  fig7   bench_butterfly          — agreement matrix, resilience, §5.3 bytes
  fig8   bench_clasp              — CLASP attribution + detection rates
  fig9   bench_incentive_stability— stability vs (T_s, gamma)
  §2     bench_codecs             — compressed-sharing codec table
  §2.1   bench_swarm              — B_eff / straggler / store traffic
  kernels bench_kernels           — VMEM working sets + oracle throughput
  §4     bench_pipeline           — schedules x wire codecs -> BENCH_pipeline.json
  §Roofline bench_roofline        — dry-run roofline table
  chaos  bench_chaos              — fault-injection scenario matrix ->
                                    BENCH_chaos.json (docs/CHAOS.md)
  serve  bench_serve              — decode tok/s + latency vs lanes ->
                                    BENCH_serve.json (docs/SERVE.md)

Usage:
  python -m benchmarks.run [module-substring]
  python -m benchmarks.run --quick    # pipeline + butterfly benches only,
                                      # reduced budget, then validate the
                                      # JSON artifact schemas
"""
from __future__ import annotations

import os
import sys
import time
import traceback

# examples self-insert src/; the harness does the same so the smoke gate
# (`python -m benchmarks.run --quick`) works without PYTHONPATH=src
_SRC = os.path.abspath(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src"))
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

# The modules that spawn CPU-pinned JAX children (forced host devices) run
# before any module that runs JAX in this process: on a TPU host that
# process then holds the chip, and a chip belongs to one process.
MODULES = [
    "benchmarks.bench_pipeline",
    "benchmarks.bench_roofline",
    "benchmarks.bench_convergence",
    "benchmarks.bench_butterfly",
    "benchmarks.bench_clasp",
    "benchmarks.bench_incentive_stability",
    "benchmarks.bench_codecs",
    "benchmarks.bench_swarm",
    "benchmarks.bench_kernels",
    "benchmarks.bench_chaos",
    "benchmarks.bench_serve",
]


def main() -> None:
    args = sys.argv[1:]
    quick = "--quick" in args
    args = [a for a in args if a != "--quick"]
    only = args[0] if args else None
    modules = MODULES
    if quick:
        # the fast CI gate: exercise the pipeline grid and the
        # store-and-forward butterfly sync at a reduced budget and
        # hard-validate both artifact schemas.  A module filter would
        # skip the benches and then validate stale/missing artifacts, so
        # it is ignored here.
        if only:
            print(f"# --quick runs only the artifact gates; "
                  f"ignoring filter {only!r}", flush=True)
            only = None
        os.environ["BENCH_QUICK"] = "1"
        modules = ["benchmarks.bench_pipeline", "benchmarks.bench_butterfly",
                   "benchmarks.bench_chaos", "benchmarks.bench_serve"]
    failures = 0
    for mod_name in modules:
        if only and only not in mod_name:
            continue
        t0 = time.time()
        print(f"# === {mod_name} ===", flush=True)
        try:
            mod = __import__(mod_name, fromlist=["run"])
            mod.run()
        except Exception:  # noqa: BLE001 — keep the harness going
            traceback.print_exc()
            failures += 1
        print(f"# {mod_name} done in {time.time()-t0:.1f}s", flush=True)
    if quick and not failures:
        from benchmarks.bench_butterfly import (
            validate_artifact as validate_butterfly)
        from benchmarks.bench_pipeline import validate_artifact
        art = validate_artifact()
        print(f"# BENCH_pipeline.json schema OK "
              f"({len(art['benchmarks'])} records)", flush=True)
        art = validate_butterfly()
        print(f"# BENCH_butterfly.json schema OK "
              f"({len(art['benchmarks'])} records, "
              f"rel_err={art['derived']['max_rel_err']})", flush=True)
        from benchmarks.bench_chaos import (
            validate_artifact as validate_chaos)
        art = validate_chaos()
        print(f"# BENCH_chaos.json schema OK "
              f"({len(art['scenarios'])} scenarios, "
              f"all_converged={art['derived']['all_converged']})",
              flush=True)
        from benchmarks.bench_serve import (
            validate_artifact as validate_serve)
        art = validate_serve()
        print(f"# BENCH_serve.json schema OK "
              f"({len(art['rows'])} rows, "
              f"best_tok_per_s={art['derived']['best_tok_per_s']})",
              flush=True)
    if failures:
        raise SystemExit(f"{failures} benchmark modules failed")


if __name__ == "__main__":
    main()
