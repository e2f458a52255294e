"""The program's host spans (``repro.common.span``) in a profiler trace.

One epoch of a small in-process sharded swarm runs inside
``jax.profiler.start_trace``; the trace is read back with
``jax.profiler.ProfileData``.  Every ``iota.*`` span of the epoch's
timeline, training, store, sharing/sync and audit layers must be there,
nested as the calls nest, with the store's read sizes as span metadata.
The same epoch run without a trace must give bit-identical results: the
spans only mark host calls.
"""
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.api import Swarm, SwarmConfig
from repro.common import SPAN_PREFIX
from repro.configs import get, smoke_variant
from repro.runtime import StateStore

CONFIG = dict(seed=0, n_stages=2, miners_per_stage=2, inner_steps=3,
              b_min=0, validators=1, sync_mode="sharded")

# every span the epoch's layers open, by layer
SPANS = {
    "timeline": ["epoch", "phase.training", "phase.validation",
                 "phase.sharing", "phase.sync", "phase.reduce_audit",
                 "finalize", "snapshot"],
    "training": ["tick", "batch"],
    "stage programs": ["miner.forward", "miner.backward",
                       "miner.backward_last", "optimizer"],
    "store": ["store.put", "store.get", "store.encode", "store.copy",
              "store.hash"],
    "sharing/sync": ["share.vector", "share.upload", "sync.reduce",
                     "sync.collect", "sync.outer_step",
                     "sync.anchor_publish", "sync.anchor_load"],
    "validation/audit": ["validate", "validate.restore", "audit.reduce",
                         "audit.compare"],
}


def _mcfg():
    return dataclasses.replace(smoke_variant(get("llama3.2-1b")).model,
                               n_layers=2)


def _epoch():
    """(stats, swarm) of one epoch of a fresh swarm."""
    swarm = Swarm.create(_mcfg(), SwarmConfig(**CONFIG))
    return swarm.run_epoch(), swarm


def _events(log_dir: str) -> list:
    """[name, start_ns, end_ns, line, stats] of every ``iota.*`` event."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    out.append([e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                (plane.name, i), dict(e.stats)])
    return out


def _downloaded(swarm) -> int:
    return sum(swarm.store.traffic_report()["downloaded"].values())


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    plain = _epoch()
    log_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        traced = _epoch()
        # a put with a codec, which the in-process swarm never makes, then
        # one read of it
        store = StateStore()
        store.put("weights/x", np.arange(1024, dtype=np.float32),
                  codec="int8")
        store.get("weights/x")
    finally:
        jax.profiler.stop_trace()
    return dict(plain=plain, traced=traced, events=_events(log_dir),
                epoch_downloaded=_downloaded(traced[1]),
                entry_bytes=store.get_entry("weights/x").nbytes)


def _parent(ev, events):
    """The innermost other ``iota.*`` span on ``ev``'s thread around it."""
    best = None
    for other in events:
        if other is ev or other[3] != ev[3]:
            continue
        if other[1] <= ev[1] and ev[2] <= other[2] and (
                best is None or other[2] - other[1] < best[2] - best[1]):
            best = other
    return best


def _chain(ev, events) -> list:
    out = []
    while True:
        ev = _parent(ev, events)
        if ev is None:
            return out
        out.append(ev[0][len(SPAN_PREFIX):])


@pytest.mark.parametrize("layer", sorted(SPANS))
def test_every_span_of_the_layer_appears(runs, layer):
    names = {e[0] for e in runs["events"]}
    missing = [n for n in SPANS[layer] if SPAN_PREFIX + n not in names]
    assert not missing, missing


def test_store_hash_nests_down_from_the_epoch(runs):
    """hash < put < miner.forward < tick < phase.training < epoch."""
    want = ["store.put", "miner.forward", "tick", "phase.training",
            "epoch"]
    chains = [_chain(e, runs["events"]) for e in runs["events"]
              if e[0] == "iota.store.hash"]
    assert want in [c[:len(want)] for c in chains], chains[:4]
    # every hash and copy sits directly inside a put
    for name in ("iota.store.hash", "iota.store.copy"):
        for e in runs["events"]:
            if e[0] == name:
                assert _chain(e, runs["events"])[0] == "store.put"


def test_sync_reduce_sits_in_the_sync_phase(runs):
    chains = [_chain(e, runs["events"]) for e in runs["events"]
              if e[0] == "iota.sync.reduce"]
    assert chains
    for c in chains:
        assert "phase.sync" in c and c[-1] == "epoch", c


def test_store_encode_sits_in_its_put(runs):
    for e in runs["events"]:
        if e[0] == "iota.store.encode":
            assert _chain(e, runs["events"])[0] == "store.put"


def test_store_get_carries_the_entry_bytes(runs):
    gets = [e for e in runs["events"] if e[0] == "iota.store.get"]
    assert gets and all("bytes" in e[4] for e in gets)
    # the last get is the codec entry's, read outside the epoch
    assert gets[-1][4]["bytes"] == runs["entry_bytes"]
    # each get counts its entry's nbytes, as the store's traffic does
    assert sum(e[4]["bytes"] for e in gets[:-1]) \
        == runs["epoch_downloaded"]


def _anchors(swarm) -> list:
    return [np.asarray(ravel_pytree(jax.tree.map(
        lambda x: x.astype(jnp.float32), a))[0]) for a in swarm.anchors]


def test_tracing_changes_no_result(runs):
    (plain, plain_swarm), (traced, traced_swarm) = runs["plain"], \
        runs["traced"]
    assert traced.merged_stages == plain.merged_stages == CONFIG["n_stages"]
    assert traced.mean_loss == plain.mean_loss
    # CLASP's per-miner mean of the losses of the ticks it served
    np.testing.assert_array_equal(traced.clasp.mean_loss,
                                  plain.clasp.mean_loss)
    assert [(r.checked, r.passed, r.min_cosine) for r in traced.validation] \
        == [(r.checked, r.passed, r.min_cosine) for r in plain.validation]
    for a, b in zip(_anchors(traced_swarm), _anchors(plain_swarm)):
        np.testing.assert_array_equal(a, b)
    for uid, m in plain_swarm.miners.items():
        theirs = traced_swarm.miners[uid]
        for a, b in zip(jax.tree.leaves((theirs.params, theirs.opt_state)),
                        jax.tree.leaves((m.params, m.opt_state))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
