"""The swarm's inner AdamW step (``runtime.miner.inner_optimizer``).

One jitted program per update, shared by every miner and every replay:
it must compute what the eager ``adamw().update`` computes, give a
validator's replay the miner's own bits, trace once per stage's parameter
shapes, and donate only state that nothing else still reads.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Swarm, SwarmConfig
from repro.api.phases import TrainingPhase
from repro.configs import get, smoke_variant
from repro.configs.base import TrainConfig
from repro.optim import adamw, cosine_warmup
from repro.runtime import miner as miner_mod

# warm-up for 10 steps, then the cosine part, over 30 steps
TC = TrainConfig(lr=1e-3, warmup_steps=10)
CONFIG = dict(seed=0, n_stages=2, miners_per_stage=2, inner_steps=3,
              b_min=0, validators=1, sync_mode="sharded")


def _mcfg():
    return dataclasses.replace(smoke_variant(get("llama3.2-1b")).model,
                               n_layers=2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_jitted_update_matches_the_eager_update(dtype):
    eager = adamw(cosine_warmup(TC.lr, TC.warmup_steps, 10_000),
                  beta1=TC.beta1, beta2=TC.beta2,
                  weight_decay=TC.weight_decay)
    init, update = miner_mod.inner_optimizer(TC)
    k = jax.random.key(0)
    p0 = {"w": jax.random.normal(k, (64, 96)).astype(dtype),
          "b": jnp.linspace(-1.0, 1.0, 96).astype(dtype)}
    pe, se = p0, eager.init(p0)
    pj, sj = jax.tree.map(jnp.copy, p0), init(p0)
    step = jnp.zeros((), jnp.int32)
    for i in range(30):
        g = jax.tree.map(
            lambda p, j: jax.random.normal(jax.random.fold_in(k, 100 * i + j),
                                           p.shape).astype(dtype),
            p0, {"w": 1, "b": 2})
        pe, se = eager.update(g, se, pe, step)
        pj, sj, step = update(g, sj, pj, step)
    assert int(step) == 30
    for a, b in zip(jax.tree.leaves(pj), jax.tree.leaves(pe)):
        assert a.dtype == b.dtype == dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=1e-5)
    # the moments are f32 whatever the leaf: the fused program differs from
    # the eager ops in their last places, relatively most where a first
    # moment is near zero, so they are held to rtol 1e-4 over a floor of
    # 1e-6 (a moment's scale is about 0.1 here)
    for a, b in zip(jax.tree.leaves(sj), jax.tree.leaves(se)):
        assert a.dtype == b.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_one_program_serves_every_caller_of_the_same_hyperparameters():
    a = miner_mod.inner_optimizer(TC)
    assert miner_mod.inner_optimizer(dataclasses.replace(TC, seed=7)) is a
    assert miner_mod.inner_optimizer(
        dataclasses.replace(TC, lr=2e-3))[1] is not a[1]


class _Replay:
    """Phase after training: the validator replays every trained miner
    from its epoch-start snapshot, keeping what the replay's last update
    returned beside the miner's own state."""
    name = "replay"

    def __init__(self):
        self.pairs = []

    def run(self, swarm, state):
        v = swarm.validators[0]
        for uid, m in swarm.miners.items():
            if not m.batches_done:
                continue
            last, shared = {}, m.update

            def record(*args):
                last["out"] = shared(*args)
                return last["out"]
            m.update = record
            try:
                res = v.validate_epoch(m, state.snapshots[uid], state.epoch,
                                       0.0, state.labels_for)
            finally:
                m.update = shared
            self.pairs.append((res, m.batches_done, last["out"],
                               (m.params, m.opt_state, m.inner_step)))


def test_validator_replay_ends_on_the_miners_bits():
    replay = _Replay()
    swarm = Swarm.create(_mcfg(), SwarmConfig(**CONFIG),
                         phases=[TrainingPhase(), replay])
    swarm.run_epoch()
    assert len(replay.pairs) >= 2
    for res, done, theirs, mine in replay.pairs:
        assert res.passed == res.checked == done
        for a, b in zip(jax.tree.leaves(theirs), jax.tree.leaves(mine)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_update_traces_once_per_stage_and_donates_safely():
    swarm = Swarm.create(_mcfg(), SwarmConfig(**CONFIG))
    t0 = miner_mod.UPDATE_TRACES
    # training, validation, int8 share, sharded sync, reduce audit
    first = swarm.run_epoch()
    structures = {jax.tree.structure(m.params)
                  for m in swarm.miners.values()}
    assert len(structures) == 2
    assert miner_mod.UPDATE_TRACES - t0 <= len(structures)
    assert first.validation and np.isfinite(first.mean_loss)

    t1 = miner_mod.UPDATE_TRACES
    second = swarm.run_epoch()
    assert miner_mod.UPDATE_TRACES == t1
    assert np.isfinite(second.mean_loss)

    # the host snapshot keeps its values through the donating update
    m = next(iter(swarm.miners.values()))
    snap = m.snapshot()
    before = jax.tree.map(np.array, snap)
    grads = jax.tree.map(jnp.ones_like, m.params)
    m._apply(grads)
    m._apply(grads)
    for a, b in zip(jax.tree.leaves(snap), jax.tree.leaves(before)):
        np.testing.assert_array_equal(a, b)
    assert any(not np.array_equal(np.asarray(a), b) for a, b in zip(
        jax.tree.leaves(m.params), jax.tree.leaves(before["params"])))
    assert miner_mod.UPDATE_TRACES == t1
