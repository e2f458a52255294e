"""The trace reducers, on a small trace recorded on one TPU v5e.

``data/trace_small.json.gz`` is the reduced form (``bench/lib/trace.py``)
of a short stretch of a traced ``swarm-train`` window: the chip's ops and
programs, and the benchmark's host spans.  Each reducer is checked against
a second, plain computation of the same quantity.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_trace.py
"""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.lib import trace as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "trace_small.json.gz")


@pytest.fixture(scope="module")
def small():
    t = tr.read_saved(DATA)
    lo, hi = tr.window_of(t)
    return t, lo, hi


def sweep_busy(ops, lo, hi) -> float:
    """Busy nanoseconds by an event sweep: +1 at each start, -1 at each
    end, busy wherever the count is positive."""
    pts = []
    for _, s, d, *_ in ops:
        s, e = max(s, lo), min(s + d, hi)
        if e > s:
            pts += [(s, 1), (e, -1)]
    pts.sort(key=lambda p: (p[0], -p[1]))
    busy, depth, last = 0.0, 0, None
    for t, step in pts:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_trace_has_a_device_and_the_window(small):
    t, lo, hi = small
    assert t["devices"] and hi > lo
    dev = sorted(t["devices"])[0]
    assert len(t["devices"][dev]["ops"]) > 100


def test_only_chips_count_as_devices(small):
    """The TPU's trace also has a "/device:CUSTOM:Megascale Trace" plane
    with an empty ops line: averaging over it would halve the busy time."""
    t, _, _ = small
    planes = sorted(t["lines"])
    assert "/device:CUSTOM:Megascale Trace" in planes
    assert [p for p in planes if tr.is_chip(p)] == sorted(t["devices"]) \
        == ["/device:TPU:0"]


def test_busy_union_matches_a_sweep(small):
    t, lo, hi = small
    dev = sorted(t["devices"])[0]
    want = sweep_busy(t["devices"][dev]["ops"], lo, hi)
    got = sum(e - s for s, e in tr.busy(t, dev, lo, hi))
    assert got == pytest.approx(want, rel=1e-9)
    assert tr.busy_seconds(t, lo, hi) == pytest.approx(want / 1e9, rel=1e-9)


def test_gaps_and_busy_tile_the_window(small):
    t, lo, hi = small
    dev = sorted(t["devices"])[0]
    busy = sum(e - s for s, e in tr.busy(t, dev, lo, hi))
    idle = sum(e - s for s, e in tr.gaps(t, dev, lo, hi))
    assert busy + idle == pytest.approx(hi - lo, rel=1e-9)


def test_idle_gaps_are_the_longest_and_named_by_their_span(small):
    t, lo, hi = small
    dev = sorted(t["devices"])[0]
    lengths = sorted(((e - s) / 1e9 for s, e in tr.gaps(t, dev, lo, hi)),
                     reverse=True)
    got = tr.idle_gaps(t, lo, hi, top=5)
    assert [g[1] for g in got] == pytest.approx(lengths[:5])
    spans = {r[0] for r in t["host"]} | {"outside any span"}
    assert all(g[0] in spans for g in got)


def test_module_seconds_sum_to_program_time(small):
    t, lo, hi = small
    dev = sorted(t["devices"])[0]
    total = sum(min(s + d, hi) - max(s, lo) for _, s, d in
                t["devices"][dev]["modules"] if min(s + d, hi) > max(s, lo))
    rows = tr.module_seconds(t, lo, hi, top=10**9)
    assert sum(r[1] for r in rows) == pytest.approx(total / 1e9, rel=1e-9)
    assert [r[1] for r in rows] == sorted((r[1] for r in rows), reverse=True)
    assert all("(" not in r[0] for r in rows)


def test_kernel_events_are_found_by_name(small):
    """The flash kernel's calls, as the trace names them (see
    ``metrics/flash_attention_roofline.swarm.py``)."""
    t, lo, hi = small

    def flash(name):
        return "closed_call" in name and "= bf16[4,32,1024,80]" in name

    rows = tr.events(t, "ops", flash, lo, hi)
    want = [r for d in t["devices"].values() for r in d["ops"]
            if flash(r[0]) and r[1] >= lo and r[1] + r[2] <= hi]
    assert len(rows) == len(want) > 0
    assert np.all([r[2] > 0 for _, r in rows])


# ---------------------------------------------------------------------------
# the program's spans, and the pipeline's readers on a synthetic trace
# ---------------------------------------------------------------------------


def test_load_keeps_program_spans_with_their_bytes(tmp_path):
    """A profile taken here on the CPU, with spans of both kinds: the
    benchmark's keep their short name, the program's their full one and
    their ``bytes``; spans of neither kind are dropped."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("iota.tick"):
            with jax.profiler.TraceAnnotation("iota.store.hash",
                                              bytes=123456):
                jnp.ones(8).block_until_ready()
        with jax.profiler.TraceAnnotation("elsewhere.span"):
            pass
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path))
    rows = {r[0]: r for r in t["host"]}
    assert set(rows) == {"window", "iota.tick", "iota.store.hash"}
    assert rows["iota.store.hash"][3] == 123456.0
    assert len(rows["iota.tick"]) == len(rows["window"]) == 3
    lo, hi = tr.window_of(t)
    assert (lo, hi) == (rows["window"][1], rows["window"][1]
                        + rows["window"][2])
    inner = rows["iota.store.hash"]
    assert tr.span_at(t, inner[1] + inner[2] / 2) == "iota.store.hash"


def test_program_spans_change_no_reduction(small):
    """The recorded trace with program spans added (one carrying bytes):
    the window and every device reduction read as before, and a gap
    inside a program span is named by it."""
    t, lo, hi = small
    dev = sorted(t["devices"])[0]
    gap = max(tr.gaps(t, dev, lo, hi), key=lambda g: g[1] - g[0])
    more = dict(t, host=t["host"] + [
        ["iota.store.copy", gap[0], gap[1] - gap[0], 4096.0],
        ["iota.tick", lo, hi - lo]])
    assert tr.window_of(more) == (lo, hi)
    assert tr.busy_seconds(more, lo, hi) == tr.busy_seconds(t, lo, hi)
    assert tr.module_seconds(more, lo, hi) == tr.module_seconds(t, lo, hi)
    assert [g[1] for g in tr.idle_gaps(more, lo, hi)] == \
        [g[1] for g in tr.idle_gaps(t, lo, hi)]
    assert tr.idle_gaps(more, lo, hi, top=1)[0][0] == "iota.store.copy"


def four_chip_trace(seed: int = 0):
    """Four chips' ops over a window of 1 ms: compute fusions, and the
    collectives of a pipeline step as their HLO text names them, laid out
    at random so that they overlap each other and the window's ends."""
    rng = np.random.default_rng(seed)
    names = {
        "compute": "%fusion.7 = bf16[1,2048,4096]{2,1,0} fusion(bf16[1,2048,"
                   "4096]{2,1,0} %p), kind=kLoop",
        "permute": "%collective-permute-done.1 = bf16[1,2048,32]{2,1,0} "
                   "collective-permute-done(bf16[1,2048,32]{2,1,0} "
                   "%collective-permute-start.1)",
        "start": "%collective-permute-start.1 = (bf16[1,2048,32]{2,1,0}, "
                 "bf16[1,2048,32]{2,1,0}) collective-permute-start("
                 "bf16[1,2048,32]{2,1,0} %code), source_target_pairs="
                 "{{0,1},{1,2},{2,3}}",
        "reduce": "%all-reduce.3 = f32[4096]{0} all-reduce(f32[4096]{0} "
                  "%g), replica_groups={{0,1,2,3}}, to_apply=%add",
        "named": "%copy.collective-permute.4 = f32[8]{0} copy(f32[8]{0} %x)",
        "loop": "%while.2 = (s32[], bf16[1,2048,32]{2,1,0}) while((s32[], "
                "bf16[1,2048,32]{2,1,0}) %tuple), condition=%cond, body=%body",
    }
    devices = {}
    for d in range(4):
        ops = []
        for kind in rng.choice(list(names), 60):
            s = float(rng.uniform(-1e5, 1.05e6))
            ops.append([names[kind], s, float(rng.uniform(1e3, 6e4)), ""])
        devices[f"/device:TPU:{d}"] = {"ops": ops, "modules": []}
    return {"devices": devices, "host": [["window", 0.0, 1e6]], "lines": {}}


def sweep_alone(ops, is_coll, lo, hi) -> float:
    """Nanoseconds in which a collective runs and nothing else but a
    control-flow op (``while``, ``conditional``), by a sweep over every op
    boundary."""
    ops = [r for r in ops if not r[0].startswith(("%while", "%conditional"))]
    pts = sorted({lo, hi} | {min(max(x, lo), hi) for _, s, d, *_ in ops
                             for x in (s, s + d)})
    alone = 0.0
    for a, b in zip(pts, pts[1:]):
        mid = (a + b) / 2
        on = [is_coll(n) for n, s, d, *_ in ops if s <= mid < s + d]
        if on and all(on):
            alone += b - a
    return alone


def read_metric(name: str, trace) -> float:
    from bench import run
    lo, hi = tr.window_of(trace)
    reader = run.load_module(os.path.join(ROOT, "bench", "metrics",
                                          name + ".py"), name)
    return reader.read(run.Readings(trace, lo, hi, {}, {}, 4)), reader


PIPE4 = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "trace_pipe4_slice.json.gz")


@pytest.mark.parametrize("seed", [0, 1, 2, "recorded"])
def test_collective_exposed_share_matches_a_sweep(seed):
    """On synthetic traces, and on 0.4 s of a traced ``pipe4-1f1b`` window
    recorded on a v5e 2x2 (``data/trace_pipe4_slice.json.gz``), where the
    scan's ``while`` and the slots' ``conditional`` span the other ops."""
    t = tr.read_saved(PIPE4) if seed == "recorded" else four_chip_trace(seed)
    lo, hi = tr.window_of(t)
    got, reader = read_metric("collective_exposed_share.pipeline", t)

    def is_coll(name):
        return bool(reader.OPCODE.search(name))

    assert is_coll("%all-reduce.3 = f32[] all-reduce(f32[] %g)")
    assert is_coll("%x = bf16[2]{0} collective-permute-done(bf16[2] %y)")
    assert not is_coll("%copy.collective-permute.4 = f32[8]{0} copy(%x)")
    want = np.mean([sweep_alone(d["ops"], is_coll, lo, hi)
                    for d in t["devices"].values()]) / (hi - lo)
    assert 0 < got < 100
    assert got == pytest.approx(100 * want, rel=1e-9)


def not_control(ops) -> list:
    return [r for r in ops
            if not r[0].startswith(("%while", "%conditional", "%call"))]


@pytest.mark.parametrize("seed", [0, 1, "recorded"])
def test_idle_share_pipeline_averages_the_chips(seed):
    """Idle is where no op but a control-flow wrapper runs: on the
    recorded slice the scan's ``while`` spans the whole window, and the
    gaps between a slot's ops still count."""
    t = tr.read_saved(PIPE4) if seed == "recorded" else four_chip_trace(seed)
    lo, hi = tr.window_of(t)
    got, _ = read_metric("idle_share.pipeline", t)
    busy = np.mean([sweep_busy(not_control(d["ops"]), lo, hi)
                    for d in t["devices"].values()])
    assert 0 < got < 100
    assert got == pytest.approx(100 * (1 - busy / (hi - lo)), rel=1e-9)
    if seed == "recorded":
        every = np.mean([sweep_busy(d["ops"], lo, hi)
                         for d in t["devices"].values()])
        assert every > 0.999 * (hi - lo), "the while op spans the slice"
        assert got > 100 * (1 - every / (hi - lo))


def test_idle_share_readers_are_one_reduction(small):
    t, lo, hi = small
    swarm, _ = read_metric("idle_share.swarm", t)
    pipe, _ = read_metric("idle_share.pipeline", t)
    assert swarm == pipe == tr.idle_share(t, lo, hi)
    assert swarm == pytest.approx(
        100 * (1 - sweep_busy(t["devices"][sorted(t["devices"])[0]]["ops"],
                              lo, hi) / (hi - lo)), rel=1e-9)
