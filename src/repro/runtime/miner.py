"""Miner: one layer-slice worker (paper §2.2).

Holds stage params + a local inner optimizer (the DiLoCo inner loop), streams
activations through its Transport (in-process or simulated-network), keeps a
local work log that validators can replay bit-exactly.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, TYPE_CHECKING

import jax
from jax.flatten_util import ravel_pytree
import jax.numpy as jnp
import numpy as np

from repro.common import span, tree_cast
from repro.configs.base import TrainConfig
from repro.optim import adamw
from repro.optim.schedules import cosine_warmup
from repro.runtime import stage_model as sm

if TYPE_CHECKING:
    from repro.api.transport import Transport

# Times the shared inner update (``inner_optimizer``) was traced, counted
# inside its traced body: tests read it to tell one cached program per
# stage's parameter shapes from a re-trace on every call.
UPDATE_TRACES = 0


@functools.lru_cache(maxsize=None)
def _inner_optimizer(lr: float, warmup_steps: int, beta1: float,
                     beta2: float, weight_decay: float):
    opt = adamw(cosine_warmup(lr, warmup_steps, 10_000), beta1=beta1,
                beta2=beta2, weight_decay=weight_decay)

    def update(grads, opt_state, params, inner_step):
        global UPDATE_TRACES
        UPDATE_TRACES += 1
        params, opt_state = opt.update(grads, opt_state, params, inner_step)
        return params, opt_state, inner_step + 1

    return opt.init, jax.jit(update, donate_argnums=(1, 2))


def inner_optimizer(tc: TrainConfig) -> tuple[Callable, Callable]:
    """``(init, update)`` of the swarm's inner AdamW at ``tc``'s
    hyperparameters.  ``update(grads, opt_state, params, inner_step)``
    returns ``(params, opt_state, inner_step + 1)`` from one jitted program
    with the schedule and bias corrections inside, and donates
    ``opt_state`` and ``params``: pass only arrays the caller owns and
    drops.  One ``update`` serves every miner and every replay of the same
    hyperparameters, so a validator's replay follows the miner's bits."""
    return _inner_optimizer(tc.lr, tc.warmup_steps, tc.beta1, tc.beta2,
                            tc.weight_decay)


@dataclasses.dataclass
class WorkItem:
    """One forward(+backward) unit, logged for validator replay."""
    tick: int
    sample_key: str          # store key of the input activation / tokens
    out_key: str             # store key of this miner's uploaded output
    did_backward: bool = False


@dataclasses.dataclass
class ReduceWorkItem:
    """One butterfly reduce unit (sharded sync): the shard-upload keys this
    miner downloaded and the reduced-copy key it re-uploaded.  Logged so
    CLASP/replay cover reduce work the same way forward/backward work is
    covered — a validator recomputes the masked merge from the same store
    inputs and compares against the uploaded copy."""
    shard: int
    in_keys: tuple[str, ...]
    out_key: str


class Miner:
    def __init__(self, uid: int, stage: int, spec: sm.SwarmModelSpec,
                 params: Any, transport: "Transport",
                 train_cfg: Optional[TrainConfig] = None):
        self.uid = uid
        self.stage = stage
        self.spec = spec
        self.role = spec.role(stage)
        self.transport = transport
        self.params = params
        init, self.update = inner_optimizer(
            train_cfg or TrainConfig(lr=1e-3, warmup_steps=20))
        self.opt_state = init(params)
        self.inner_step = jnp.zeros((), jnp.int32)
        self.batches_done = 0
        self.work_log: list[WorkItem] = []
        self.reduce_log: list[ReduceWorkItem] = []
        self._pending: dict[str, Any] = {}     # sample_key -> input (for bwd)

    # ------------------------------------------------------------------

    @property
    def actor(self) -> str:
        return f"miner{self.uid}"

    def forward(self, tick: int, sample_key: str, out_key: str) -> Any:
        """Read input from the store, apply the stage, upload the output."""
        with span("miner.forward"):
            x_in = self.transport.get(sample_key, actor=self.actor)
            out = sm.stage_forward(self.params, x_in, self.spec, self.role)
            self._pending[sample_key] = x_in
            self.transport.put(out_key, out, actor=self.actor)
            self.work_log.append(WorkItem(tick, sample_key, out_key))
            return out

    def backward_last(self, sample_key: str, labels) -> tuple[float, Any]:
        """Last-stage miner: compute loss + grads, return (loss, g_z_in)."""
        with span("miner.backward_last"):
            z_in = self._pending.pop(sample_key)
            loss, g_params, g_z = sm.last_stage_loss_and_grads(
                self.params, z_in, labels, self.spec)
            self._apply(g_params)
            return float(loss), g_z

    def backward(self, sample_key: str, g_out) -> Any:
        """Mid/first miner: VJP through the recomputed stage forward."""
        with span("miner.backward"):
            x_in = self._pending.pop(sample_key)
            g_params, g_x = sm.stage_backward(self.params, x_in, g_out,
                                              self.spec, self.role)
            self._apply(g_params)
            return g_x

    def _apply(self, grads) -> None:
        with span("optimizer") as s:
            traces = UPDATE_TRACES
            self.params, self.opt_state, self.inner_step = self.update(
                grads, self.opt_state, self.params, self.inner_step)
            s.set_metadata(traced=UPDATE_TRACES - traces)
        self.batches_done += 1
        if self.work_log:
            self.work_log[-1].did_backward = True

    # ------------------------------------------------------------------
    # weight exchange (flattened fp32 vector, per paper §5.1 sharding)
    # ------------------------------------------------------------------

    def weights_vector(self) -> np.ndarray:
        with span("share.vector"):
            flat, _ = ravel_pytree(
                jax.tree.map(lambda x: x.astype(jnp.float32), self.params))
            return np.asarray(flat)

    def run_reduce(self, executor, idx: int, tamper: float = 0.0) -> int:
        """Perform this miner's assigned butterfly reduce work through the
        store (``executor`` is a ``core.butterfly.ButterflyExecutor``; this
        miner is plan index ``idx``).  Every download/upload is charged to
        this miner's link.  ``tamper`` is the fault-injection hook (a
        deceptive reducer offsets its copies).  Returns shards reduced."""
        done = executor.run_reducer(idx, actor=self.actor, tamper=tamper)
        self.reduce_log.extend(
            ReduceWorkItem(a.shard, a.upload_keys, a.reduced_key)
            for a in done)
        return len(done)

    def load_weights_vector(self, vec: np.ndarray) -> None:
        with span("sync.anchor_load"):
            flat, unravel = ravel_pytree(
                jax.tree.map(lambda x: x.astype(jnp.float32), self.params))
            new = unravel(jnp.asarray(vec, jnp.float32))
            self.params = jax.tree.map(lambda n, p: n.astype(p.dtype),
                                       new, self.params)

    def reset_epoch(self) -> None:
        self.batches_done = 0
        self.work_log = []
        self.reduce_log = []
        self._pending = {}

    def snapshot(self) -> dict:
        """State a validator copies at full sync to track this miner, in
        host memory: it stays cold until one validator replays it, and on
        device the epoch-start copies of every miner sharing a chip would
        double what the swarm keeps there."""
        with span("snapshot"):
            return jax.device_get({"params": self.params,
                                   "opt_state": self.opt_state,
                                   "inner_step": self.inner_step})
