"""Readings that the limits of ``correct`` are set from, for one cell.

    python bench/calibrate.py --workload <cell> --seeds 11 12 13 ... \
        [--control-seeds 11 12 13] [--fault-seeds 11 12 13]

For every seed, in one process: the cell's set-up (which drives the
program through its first steps, as every run does), then the plain
reference, and the numbers the check compares.  For each control seed,
the reference in the control's precision (``fp8``) takes the program's
place over the same steps, and the same numbers are read against the
float32 reference; for each fault seed, so does the float32 reference
with each planted fault its driver lists (``cell.faults``: the half batch,
and in the pipeline the schedule's drain left out).  Prints one JSON line
per reading; no window is run.
The limits in ``bench/workloads/<cell>.json`` lie between the largest
program reading and the smallest control reading.  The reference has no
validator, so the control's and the fault's lines carry no validator
numbers: those are the program's own, read in every run.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None, *, require_chip: bool = True, override=None) -> list:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[],
                    help="seeds on which each planted fault of the "
                         "driver (the reference on half of every batch, ...) "
                         "takes the program's place")
    ap.add_argument("--raw", default=None,
                    help="also append every reading, leaf by leaf, here")
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import run
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    files = run.cell_files(bench, args.workload)
    if override is not None:
        override(files)
    if require_chip:
        run.use_cache_dir()
    import jax
    dev = run.device_facts(jax)
    if require_chip and dev["platform"] != "tpu":
        raise run.Refused(f"no TPU here: {dev}")
    driver = run.load_module(os.path.join(
        BENCH, "drivers", files["traffic"]["driver"] + ".py"), "bench_driver")
    out = []
    seeds = set(args.seeds) | set(args.control_seeds) | set(args.fault_seeds)
    for seed in sorted(seeds):
        t0 = time.perf_counter()
        cell = driver.build(dict(files, seed=seed, chips=files["cell"]["chips"]))
        cell.setup()
        cell.release()
        want = cell.reference("f32")
        rows, raw = [], {"reference": want}
        if seed in args.seeds:
            rows.append(("program", cell.numbers(cell.prog, want)))
            raw["program"] = cell.prog
        kinds = [("control", args.control_seeds, {"mode": "fp8"})] + [
            (name, args.fault_seeds, dict(kw, mode="f32"))
            for name, kw in cell.faults.items()]
        for kind, chosen, kw in kinds:
            if seed in chosen:
                got = cell.reference(**kw)
                rows.append((kind, cell.numbers(got, want)))
                raw[kind] = got
        if args.raw:
            with open(args.raw, "a") as f:
                f.write(json.dumps({"seed": seed, **raw,
                                    "routing": getattr(cell, "routing",
                                                       None)}) + "\n")
        for kind, numbers in rows:
            line = {"workload": args.workload, "seed": seed, "kind": kind,
                    "numbers": numbers,
                    "seconds": time.perf_counter() - t0}
            print(json.dumps(line), flush=True)
            out.append(line)
        del cell, want
        gc.collect()
    return out


if __name__ == "__main__":
    main()
