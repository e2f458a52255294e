"""Run one benchmark cell once, on the accelerator this machine holds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the repository root:
the cell's configuration (``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<traffic>.json``, which names its driver,
``bench/drivers/<driver>.py``), its limits (``bench/workloads/<cell>.json``)
and, with ``--trace 1``, one reader per per-layer metric
(``bench/metrics/<metric>.py``).  Peaks come from ``bench/peaks.json``,
keyed by the device's kind.

A run is one process: set-up (build, weights from the seed, warm-up of the
shapes the window uses), the measured window, then the comparison with the
plain reference that decides ``correct``.  Without a TPU, or with fewer
chips than the cell asks for, it exits 2 and prints no result.  The last
line of standard output is the one JSON result; the numbers compared, each
beside its limit, are the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class Refused(Exception):
    """The run cannot be made here; it exits non-zero with no result."""


def use_cache_dir() -> None:
    """Keep JAX's persistent cache at ``CACHE_DIR``, and drop its entries
    that have no access-time file.  A process that writes the cache with
    eviction off leaves such entries; with eviction on, as here, JAX then
    fails every write to the cache, and every run compiles anew."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.makedirs(CACHE_DIR, exist_ok=True)
    for name in os.listdir(CACHE_DIR):
        if name.endswith("-cache") and not os.path.exists(
                os.path.join(CACHE_DIR, name[: -len("cache")] + "atime")):
            os.remove(os.path.join(CACHE_DIR, name))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find(rows: list, name: str, what: str) -> dict:
    for r in rows:
        if r["name"] == name:
            return r
    raise Refused(f"no {what} named {name!r} in BENCHMARK.json")


def cell_files(bench: dict, workload: str) -> dict:
    cell = find(bench["workloads"], workload, "workload")
    entry = find(bench["configs"], cell["config"], "configuration")
    return {"cell": cell, "config_name": entry["name"],
            "config": load_json(os.path.join(ROOT, entry["file"])),
            "traffic": load_json(os.path.join(BENCH, "traffic",
                                              cell["traffic"] + ".json")),
            "limits": load_json(os.path.join(BENCH, "workloads",
                                             workload + ".json"))["limits"]}


def per_layer_metrics(bench: dict, cell: dict) -> list:
    """The per-layer metrics this cell reports: those that list it, and
    those without a list that move an end-to-end metric it reports."""
    mine = {m["name"] for m in bench["end_to_end"]
            if cell["name"] in m.get("workloads", [cell["name"]])}
    return [m for m in bench["per_layer"]
            if cell["name"] in m.get("workloads", [cell["name"]])
            and m["moves"] in mine]


def device_facts(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(jax) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


class Readings:
    """What a per-layer reader sees: the reduced trace, the window on the
    trace's clock, the driver's counts and shapes, and the chip's peaks."""

    def __init__(self, trace, lo, hi, ctx, peaks, chips):
        self.trace, self.lo, self.hi = trace, lo, hi
        self.ctx, self.peaks, self.chips = ctx, peaks, chips


def main(argv=None, *, require_chip: bool = True, override=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default=None,
                    help="also write the reduced trace here (gzip JSON)")
    args = ap.parse_args(argv)

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    files = cell_files(bench, args.workload)
    if override is not None:
        override(files)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise Refused("the program under test (src/repro) is not here")
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    if require_chip:
        # the cache lives at a fixed path inside the checkout, whatever
        # the environment says, so that two checkouts share nothing
        use_cache_dir()

    import jax
    from bench.lib.clock import CompileClock
    from bench.lib import compare

    dev = device_facts(jax)
    chips = files["cell"]["chips"]
    if require_chip and (dev["platform"] != "tpu" or dev["count"] < chips):
        raise Refused(f"the cell needs {chips} TPU chip(s); JAX found "
                      f"{dev['count']} {dev['platform']} device(s)")
    table = load_json(os.path.join(BENCH, "peaks.json"))
    # a rehearsal off the chip runs the readers' code against the first
    # table entry; its numbers are never a device's
    if require_chip and dev["kind"] not in table:
        raise Refused(f"no peaks for device kind {dev['kind']!r} in "
                      f"bench/peaks.json")
    peaks = table[dev["kind"]] if require_chip else next(iter(table.values()))
    if require_chip:
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    clock = CompileClock().start()
    ctx = dict(files, seed=args.seed, chips=chips)
    driver = load_module(os.path.join(
        BENCH, "drivers", files["traffic"]["driver"] + ".py"),
        "bench_driver_" + files["traffic"]["driver"])
    cell = driver.build(ctx)
    cell.setup()
    # work of the check that set-up had to do while the program's state
    # was there (``check_s``) is not set-up
    setup_s = time.perf_counter() - T_START - getattr(cell, "check_s", 0.0)
    before = clock.counts()

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    if log_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    result = cell.window(args.seconds)
    if log_dir:
        jax.profiler.stop_trace()
    after = clock.counts()
    in_window = {k: after[k] - before[k] for k in ("traced", "built",
                                                   "misses")}
    in_window["names"] = clock.names[before["built"]:]
    dev["memory_peak_bytes"] = memory_peak(jax)

    metrics, breakdown = {}, None
    if log_dir:
        metrics, breakdown, busy_s, window_s = traced_metrics(
            bench, files["cell"], cell, log_dir, peaks, chips,
            args.dump_trace)
        dev["busy_s"], dev["window_s"] = busy_s, window_s
        shutil.rmtree(log_dir, ignore_errors=True)
    else:
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        for name, v in result["metrics"].items():
            metrics[name] = {"value": v, "unit": units[name]}

    cell.release()
    t_check = time.perf_counter()
    check = cell.check(files["limits"])
    print(f"bench: set-up {setup_s:.1f} s, reference and comparison "
          f"{time.perf_counter() - t_check:.1f} s", file=sys.stderr)
    correct = compare.passed(check)
    out = {"correct": correct, "attempted": result["attempted"],
           "failed": result["failed"], "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compiles"] = {"setup": before, "window": in_window}
    out["check"] = {name: {"value": v, "limit": lim}
                    for name, v, lim in check}
    print(json.dumps(out), flush=True)
    for name, v, lim in check:
        print(f"check {name} {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()
    return out


def traced_metrics(bench, cell_entry, cell, log_dir, peaks, chips, dump):
    from bench.lib import trace as tr
    trace = tr.load(log_dir)
    lo, hi = tr.window_of(trace)
    r = Readings(trace, lo, hi, cell.context(), peaks, chips)
    metrics = {}
    for m in per_layer_metrics(bench, cell_entry):
        reader = load_module(os.path.join(BENCH, "metrics",
                                          m["name"] + ".py"),
                             "bench_metric_" + m["name"].replace(".", "_"))
        v = reader.read(r)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    breakdown = {"device_ops": tr.module_seconds(trace, lo, hi),
                 "idle_gaps": tr.idle_gaps(trace, lo, hi)}
    if dump:
        tr.save(trace, dump)
    return metrics, breakdown, tr.busy_seconds(trace, lo, hi), (hi - lo) / 1e9


if __name__ == "__main__":
    try:
        main()
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        sys.exit(2)
