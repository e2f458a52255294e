"""Store hygiene regressions: prefix boundaries, retention GC, async joins.

Three latent bugs the in-process path never surfaced (found while
building the socket transport, where store hygiene is load-bearing):

  * ``delete_prefix``/``keys`` used raw ``startswith``, so the epoch-GC
    prefix ``activations/ep1`` also deleted ``activations/ep10+`` and the
    audit walk for stage ``s1`` leaked ``s10+`` keys;
  * the weights/ and scores/ planes were never garbage-collected — long
    runs grew the store without bound;
  * ``ValidationPhase`` KeyError'd on a miner registered mid-epoch (no
    epoch-start snapshot to replay from).

Also the store's content digest against a plain reference, and the size
its hash span carries.
"""
import contextlib
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro.api import (
    InProcessTransport,
    KeySchema,
    SharingPhase,
    Swarm,
    SwarmConfig,
    SyncPhase,
    TrainingPhase,
    ValidationPhase,
)
from repro.api.phases import EpochState
from repro.configs import get, smoke_variant
from repro.core import compression
from repro.runtime import StateStore, state_store


def _mcfg(n_layers=2):
    return dataclasses.replace(smoke_variant(get("llama3.2-1b")).model,
                               n_layers=n_layers)


# ---------------------------------------------------------------------------
# prefix-boundary regressions (fail on the old raw-startswith behaviour)
# ---------------------------------------------------------------------------

def _epoch_collision_store():
    store = StateStore()
    for e in (1, 10, 11, 100):
        store.put(f"activations/ep{e}/t0/tokens", np.zeros(2))
        store.put(f"activations/ep{e}/t0/s0/m0", np.zeros(2))
    return store


def test_keys_ep1_does_not_match_ep10():
    store = _epoch_collision_store()
    ks = KeySchema()
    got = store.keys(ks.activations_prefix(1))
    assert got == ["activations/ep1/t0/s0/m0", "activations/ep1/t0/tokens"]


def test_delete_prefix_ep1_leaves_ep10_alone():
    store = _epoch_collision_store()
    ks = KeySchema()
    assert store.delete_prefix(ks.activations_prefix(1)) == 2
    surviving = store.keys()
    assert len(surviving) == 6
    assert all(k.split("/")[1] in ("ep10", "ep11", "ep100")
               for k in surviving)


def test_stage_prefix_s1_does_not_match_s10():
    store = StateStore()
    ks = KeySchema(version=2)
    for s in (1, 10, 12):
        store.put(ks.shard_upload(0, s, 0, 0), np.zeros(2))
        store.put(ks.shard_reduced(0, s, 0, 1), np.zeros(2))
    got = store.keys(ks.stage_weights_prefix(0, 1))
    assert got == ["weights/ep0/s1/m0/shard0",
                   "weights/ep0/s1/shard0/reduced/m1"]
    assert store.delete_prefix(ks.stage_weights_prefix(0, 1)) == 2
    assert len(store.keys("weights/ep0")) == 4


def test_exact_key_and_trailing_slash_and_empty_prefix():
    store = StateStore()
    store.put("weights/ep1/s0/m1", np.zeros(2))
    store.put("weights/ep1/s0/m10", np.zeros(2))
    # an exact key is its own segment boundary
    assert store.keys("weights/ep1/s0/m1") == ["weights/ep1/s0/m1"]
    # trailing slash keeps its literal meaning (seed-era callers)
    assert len(store.keys("weights/")) == 2
    # empty prefix covers everything
    assert len(store.keys("")) == 2
    assert store.delete_prefix("") == 2


def test_in_process_transport_inherits_boundary_semantics():
    tp = InProcessTransport()
    tp.put("scores/ep2/v0/m1", np.zeros(1))
    tp.put("scores/ep20/v0/m1", np.zeros(1))
    assert tp.keys("scores/ep2") == ["scores/ep2/v0/m1"]
    assert tp.delete_prefix("scores/ep2") == 1
    assert tp.exists("scores/ep20/v0/m1")


# ---------------------------------------------------------------------------
# retention-window GC (weights/ + scores/ planes)
# ---------------------------------------------------------------------------

def _epochs_present(tp, namespace):
    return sorted({int(k.split("/")[1][2:]) for k in tp.keys(namespace)})


def _gc_cfg(**kw):
    # inner_steps=6 so every miner clears b_min each epoch: the weight
    # plane gets artifacts every epoch, which is what the GC must prune
    return SwarmConfig(seed=0, n_stages=2, miners_per_stage=2, inner_steps=6,
                       b_min=1, batch_size=2, seq_len=16, validators=1, **kw)


def test_default_keeps_every_epoch_for_replay():
    swarm = Swarm.create(_mcfg(), _gc_cfg())
    swarm.run(3)
    assert _epochs_present(swarm.transport, "weights/") == [0, 1, 2]
    assert _epochs_present(swarm.transport, "scores/") == [0, 1, 2]
    # activations are still GC'd per epoch, as always
    assert swarm.transport.keys("activations/") == []


def test_retention_window_bounds_the_store():
    swarm = Swarm.create(_mcfg(), _gc_cfg(retain_epochs=2))
    swarm.run(5)
    assert _epochs_present(swarm.transport, "weights/") == [3, 4]
    assert _epochs_present(swarm.transport, "scores/") == [3, 4]


def test_retention_window_one_keeps_only_current_epoch():
    swarm = Swarm.create(_mcfg(), _gc_cfg(retain_epochs=1))
    swarm.run(3)
    assert _epochs_present(swarm.transport, "weights/") == [2]
    assert _epochs_present(swarm.transport, "scores/") == [2]


def test_retained_trajectory_unchanged():
    """GC only removes *finished* epochs' artifacts: the loss trajectory
    is identical with and without a retention window."""
    keep = Swarm.create(_mcfg(), _gc_cfg()).run(3)
    gc = Swarm.create(_mcfg(), _gc_cfg(retain_epochs=1)).run(3)
    assert [s.mean_loss for s in gc] == [s.mean_loss for s in keep]


def test_retention_window_validated():
    with pytest.raises(AssertionError):
        _gc_cfg(retain_epochs=0)


# ---------------------------------------------------------------------------
# async join mid-epoch (ROADMAP scenario: blocked on a ValidationPhase bug)
# ---------------------------------------------------------------------------

def test_validation_skips_snapshotless_mid_epoch_joiner():
    """Old behaviour: ``state.snapshots[uid]`` KeyError'd the moment a
    validator's random draw picked a miner registered after epoch start."""
    swarm = Swarm.create(
        _mcfg(), SwarmConfig(seed=0, n_stages=2, miners_per_stage=1,
                             inner_steps=2, b_min=1, batch_size=2,
                             seq_len=16, validators=8),
        phases=[])
    state = EpochState(epoch=0, snapshots={u: m.snapshot()
                                           for u, m in swarm.miners.items()})
    TrainingPhase().run(swarm, state)
    joiner = swarm.register_miner(stage=0)          # mid-epoch join
    ValidationPhase().run(swarm, state)             # must not raise
    assert len(state.validation) == 8
    assert all(r.miner_uid != joiner.uid for r in state.validation)


def test_validation_no_op_when_nobody_has_a_snapshot():
    swarm = Swarm.create(
        _mcfg(), SwarmConfig(seed=0, n_stages=1, miners_per_stage=1,
                             inner_steps=1, b_min=1, batch_size=2,
                             seq_len=16, validators=2),
        phases=[])
    state = EpochState(epoch=0, snapshots={})
    ValidationPhase().run(swarm, state)
    assert state.validation == []


class _JoinPhase:
    """Scenario phase: one miner joins between training and validation."""
    name = "join"

    def __init__(self, stage: int, at_epoch: int = 0):
        self.stage = stage
        self.at_epoch = at_epoch
        self.joined: list[int] = []

    def run(self, swarm, state):
        if state.epoch == self.at_epoch:
            self.joined.append(swarm.register_miner(stage=self.stage).uid)


def test_async_join_scenario_full_timeline():
    """ROADMAP async-join scenario: a custom phase list, no core edits.
    The joiner is skipped by validators in its join epoch, receives the
    anchor at the next full sync, and is trackable from the next epoch."""
    join = _JoinPhase(stage=0)
    swarm = Swarm.create(
        _mcfg(), SwarmConfig(seed=0, n_stages=2, miners_per_stage=2,
                             inner_steps=4, b_min=1, batch_size=2,
                             seq_len=16, validators=6),
        phases=[TrainingPhase(), join, ValidationPhase(), SharingPhase(),
                SyncPhase()])
    stats = swarm.run(2)
    (uid,) = join.joined
    assert uid in swarm.miners
    # epoch 0: every verdict targets a snapshotted miner, never the joiner
    assert all(r.miner_uid != uid for r in stats[0].validation)
    assert len(stats[0].validation) == 6
    # epoch 1: the joiner has an epoch-start snapshot and is now eligible
    # (and with 6 validators over 5 miners, seed 0 does track it)
    assert any(r.miner_uid == uid for r in stats[1].validation)
    assert np.isfinite(stats[-1].mean_loss)
    # it participated in training after its first full sync
    assert swarm.miners[uid].batches_done > 0


# ---------------------------------------------------------------------------
# content digest: SHA-256 of each leaf's raw bytes, in leaf order, 96 bits
# ---------------------------------------------------------------------------

def _reference_digest(tree) -> str:
    raw = b"".join(np.ascontiguousarray(np.asarray(leaf)).tobytes()
                   for leaf in jax.tree_util.tree_leaves(tree))
    return hashlib.sha256(raw).hexdigest()[:24]


_F32 = np.arange(24, dtype=np.float32).reshape(4, 6) / 7

_DIGEST_CASES = {
    "f32": lambda: _F32,
    "transposed": lambda: _F32.T,
    "bf16": lambda: (_F32 * 3).astype(ml_dtypes.bfloat16),
    "scalar": lambda: np.float32(2.5),
    "device": lambda: jnp.asarray(_F32) + 1,
    "int8_payload": lambda: compression.encode(
        jnp.linspace(-1.0, 1.0, 300, dtype=jnp.float32), "int8"),
    "tree": lambda: {"w": _F32, "b": np.ones(3, np.int32),
                     "meta": (np.float32(1), np.zeros((2, 0), np.float32))},
}


@pytest.mark.parametrize("case", sorted(_DIGEST_CASES))
def test_digest_matches_plain_reference(case):
    tree = _DIGEST_CASES[case]()
    got = state_store._digest(tree)
    assert got == _reference_digest(tree)
    assert len(got) == 24 and int(got, 16) >= 0
    if case == "transposed":
        assert got == state_store._digest(np.ascontiguousarray(tree))
    # one byte flipped in the largest leaf changes the digest
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    i = max(range(len(leaves)), key=lambda j: np.asarray(leaves[j]).nbytes)
    flipped = np.array(leaves[i], order="C")
    flipped.reshape(-1).view(np.uint8)[0] ^= 1
    leaves[i] = flipped
    assert state_store._digest(
        jax.tree_util.tree_unflatten(treedef, leaves)) != got


@pytest.mark.parametrize("codec", [None, "int8"])
def test_hash_span_carries_the_put_size(monkeypatch, codec):
    opened = []

    def record(name, **counts):
        opened.append((name, counts))
        return contextlib.nullcontext()

    monkeypatch.setattr(state_store, "span", record)
    entry = StateStore().put("weights/ep0/s0/m0",
                             np.linspace(0, 1, 1000, dtype=np.float32),
                             codec=codec)
    assert ("store.hash", {"bytes": entry.nbytes}) in opened
    # the size hashed is the stored payload's: int8 codes, not 4000 f32 bytes
    assert (entry.nbytes == 4000) == (codec is None)
