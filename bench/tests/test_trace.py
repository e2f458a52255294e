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
