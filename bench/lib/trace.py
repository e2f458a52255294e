"""Profiler trace of a window, reduced to what the metrics read.

``load`` reads the ``.xplane.pb`` the JAX profiler wrote and keeps, on one
clock in nanoseconds:

  devices  per accelerator: its "XLA Ops" events (name, start, duration,
           and the HLO text of the op where the trace gives it) and its
           "XLA Modules" events (one per launched program)
  host     the benchmark's own spans, ``bench.*`` TraceAnnotations, under
           their name without the prefix (``window``, ``epoch``), and the
           program's, ``iota.*``, under their full name
           (``iota.store.hash``): [name, start, duration], with a fourth
           element, the span's ``bytes``, where it carries them

The reducers below work on that compact form, so a small recorded trace can
check them on the CPU (``bench/tests``).
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "iota."
# the opcode of a control-flow op in its HLO text, after the output shape
CONTROL = re.compile(r"[)}\]] (?:while|conditional|call)\(")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def _stat(event, key):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def is_chip(plane_name: str) -> bool:
    return plane_name.startswith("/device:") \
        and not plane_name.startswith("/device:CUSTOM")


def host_span(event):
    """The row ``load`` keeps of a host event, or None."""
    if event.name.startswith(SPAN_PREFIX):
        name = event.name[len(SPAN_PREFIX):]
    elif event.name.startswith(PROGRAM_PREFIX):
        name = event.name
    else:
        return None
    row = [name, float(event.start_ns), float(event.duration_ns)]
    nbytes = _stat(event, "bytes")
    if nbytes is not None:
        row.append(float(nbytes))
    return row


def load(log_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, host, lines = {}, [], {}
    for plane in data.planes:
        lines[plane.name] = [ln.name for ln in plane.lines]
        # "/device:CUSTOM:..." planes (the TPU's Megascale trace) carry an
        # empty ops line; they are no accelerator and are not averaged over
        if is_chip(plane.name) and any(
                ln.name == OPS_LINE for ln in plane.lines):
            dev = {"ops": [], "modules": []}
            for ln in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(ln.name)
                if key is None:
                    continue
                for e in ln.events:
                    row = [e.name, float(e.start_ns), float(e.duration_ns)]
                    if key == "ops":
                        row.append(_stat(e, "long_name") or "")
                    dev[key].append(row)
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    row = host_span(e)
                    if row is not None:
                        host.append(row)
    return {"devices": devices, "host": host, "lines": lines}


def save(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def read_saved(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# reducers
# ---------------------------------------------------------------------------


def union(intervals) -> list:
    """Merge [start, end) intervals; returns them sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def window_of(trace: dict, span: str = "window") -> tuple:
    """(start, end) of the host span that marks the measured window."""
    rows = [r for r in trace["host"] if r[0] == span]
    if not rows:
        raise ValueError(f"no host span {span!r} in the trace")
    s, d = rows[0][1], rows[0][2]
    return s, s + d


def clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy(trace: dict, device: str, lo: float, hi: float) -> list:
    """Disjoint intervals in [lo, hi) in which an op ran on ``device``.
    The control-flow ops (``while``, ``conditional``, ``call``), whose
    events span the ops of their bodies and the gaps between them, are not
    counted: the ops inside them are."""
    ops = [r for r in trace["devices"][device]["ops"]
           if not CONTROL.search(r[0])]
    return union(clip([[r[1], r[1] + r[2]] for r in ops], lo, hi))


def busy_seconds(trace: dict, lo: float, hi: float) -> float:
    """Busy time in [lo, hi), averaged over the devices in the trace."""
    devs = sorted(trace["devices"])
    if not devs:
        return 0.0
    tot = sum(e - s for d in devs for s, e in busy(trace, d, lo, hi))
    return tot / len(devs) / 1e9


def idle_share(trace: dict, lo: float, hi: float):
    """Share of [lo, hi), in %, in which no op ran on a chip (``busy``),
    averaged over the chips; None where there is nothing to read."""
    if hi <= lo or not trace["devices"]:
        return None
    return 100.0 * (1.0 - busy_seconds(trace, lo, hi) / ((hi - lo) / 1e9))


def overlap(a: list, b: list) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        tot += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def alone_seconds(trace: dict, match, lo: float, hi: float) -> float:
    """Time in [lo, hi) in which an op whose name satisfies ``match`` runs
    on a device and no other op does, averaged over the devices.  The
    control-flow ops (``while``, ``conditional``, ``call``), whose events
    span the ops of their bodies, count as neither."""
    devs = sorted(trace["devices"])
    if not devs:
        return 0.0
    tot = 0.0
    for d in devs:
        ops = [r for r in trace["devices"][d]["ops"]
               if not CONTROL.search(r[0])]
        mine = union(clip([[r[1], r[1] + r[2]] for r in ops if match(r[0])],
                          lo, hi))
        rest = union(clip([[r[1], r[1] + r[2]] for r in ops
                           if not match(r[0])], lo, hi))
        tot += sum(e - s for s, e in mine) - overlap(mine, rest)
    return tot / len(devs) / 1e9


def gaps(trace: dict, device: str, lo: float, hi: float) -> list:
    """Idle intervals of ``device`` in [lo, hi)."""
    out, t = [], lo
    for s, e in busy(trace, device, lo, hi):
        if s > t:
            out.append([t, s])
        t = max(t, e)
    if hi > t:
        out.append([t, hi])
    return out


def span_at(trace: dict, t: float, skip=("window",)) -> str:
    """Name of the innermost host span covering time ``t``."""
    best, best_len = "outside any span", float("inf")
    for name, s, d, *_ in trace["host"]:
        if name in skip:
            continue
        if s <= t < s + d and d < best_len:
            best, best_len = name, d
    return best


def idle_gaps(trace: dict, lo: float, hi: float, top: int = 10) -> list:
    """The ``top`` longest idle gaps of the first device, each named by the
    host span that covers its middle: [[span, seconds], ...]."""
    if not trace["devices"]:
        return []
    dev = sorted(trace["devices"])[0]
    g = sorted(gaps(trace, dev, lo, hi), key=lambda x: x[0] - x[1])[:top]
    return [[span_at(trace, (s + e) / 2), (e - s) / 1e9] for s, e in g]


def module_seconds(trace: dict, lo: float, hi: float, top: int = 10) -> list:
    """Device time by compiled program ("XLA Modules", the name without its
    fingerprint), averaged over devices: the ``top`` largest."""
    devs = sorted(trace["devices"])
    if not devs:
        return []
    tot: dict = {}
    for d in devs:
        for r in trace["devices"][d]["modules"]:
            s, e = max(r[1], lo), min(r[1] + r[2], hi)
            if e > s:
                name = r[0].split("(")[0]
                tot[name] = tot.get(name, 0.0) + (e - s)
    rows = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / len(devs) / 1e9] for k, v in rows]


def events(trace: dict, line: str, match, lo: float, hi: float) -> list:
    """Events of ``line`` ("ops" or "modules") on any device whose name
    satisfies ``match``, clipped to [lo, hi): [[device, row], ...]."""
    out = []
    for d, dev in trace["devices"].items():
        for r in dev[line]:
            if r[1] >= lo and r[1] + r[2] <= hi and match(r[0]):
                out.append([d, r])
    return out
