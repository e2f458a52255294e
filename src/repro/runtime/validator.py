"""Validator (paper §2.3, §3): computational-reproducibility auditing.

At full sync the validator copies a target miner's state; during the epoch
it re-runs the miner's logged work *in order* (forward from the same store
inputs, backward with the same gradients), comparing its own outputs to the
miner's uploads by cosine similarity.  Deviation below threshold => the
work is rejected; the epoch score S_m^n is the count of *validated*
backward passes.  Miners never know when they are tracked.

Sharded sync (§5.1-5.3, KeySchema v2) adds two reduce-audit paths:

  * ``audit_reduce``  — trustless: rebuilds the Fig 7a agreement matrix
    purely from the store's redundant reduced copies (shard identity and
    reducer uids are in the keys), flagging any reducer out of consensus
    with its partners.  No miner state or plan needed.
  * ``replay_reduce`` — replays a tracked miner's ``reduce_log`` the same
    way forward/backward work is replayed: recompute the masked merge from
    the logged store inputs, compare to the uploaded reduced copy.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import cosine_similarity, span
from repro.core import butterfly, compression
from repro.core.incentives import IncentiveLedger
from repro.kernels import ops
from repro.runtime import stage_model as sm
from repro.runtime.miner import Miner

if TYPE_CHECKING:
    from repro.api.transport import Transport

COSINE_THRESHOLD = 0.99


@dataclasses.dataclass
class ReduceAuditResult:
    """Store-side audit of one (epoch, stage) butterfly reduce."""
    epoch: int
    stage: int
    uids: list          # reducer uids seen in the store, sorted
    agreement: np.ndarray          # (len(uids), len(uids)), NaN = no shared shard
    flagged: list       # uids whose mean partner agreement < 0.5

    @property
    def clean(self) -> bool:
        return not self.flagged


@dataclasses.dataclass
class ValidationResult:
    miner_uid: int
    epoch: int
    checked: int
    passed: int
    score: float                 # validated backward passes
    min_cosine: float

    @property
    def honest(self) -> bool:
        return self.checked == 0 or self.passed == self.checked


class Validator:
    def __init__(self, uid: int, transport: "Transport",
                 ledger: IncentiveLedger):
        self.uid = uid
        self.transport = transport
        self.ledger = ledger
        self.results: list[ValidationResult] = []

    @property
    def actor(self) -> str:
        return f"validator{self.uid}"

    def validate_epoch(self, miner: Miner, snapshot: dict, epoch: int,
                       t_now: float, labels_for: dict,
                       max_items: Optional[int] = None) -> ValidationResult:
        """Replay ``miner``'s logged epoch from ``snapshot`` (its full-sync

        state).  ``labels_for`` maps sample_key -> labels (the validator
        reads the same dataset shard).  Scores are assigned per §3."""
        with span("validate"):
            with span("validate.restore"):
                params, opt_state, inner_step = jax.device_put(
                    (snapshot["params"], snapshot["opt_state"],
                     snapshot["inner_step"]))
            spec, role = miner.spec, miner.role

            checked = passed = 0
            validated_backwards = 0.0
            min_cos = 1.0
            items = miner.work_log if max_items is None \
                else miner.work_log[:max_items]
            for item in items:
                x_in = self.transport.get(item.sample_key, actor=self.actor)
                mine = sm.stage_forward(params, x_in, spec, role)
                theirs = self.transport.get(item.out_key, actor=self.actor)
                cos = float(cosine_similarity(
                    jnp.asarray(mine, jnp.float32),
                    jnp.asarray(theirs, jnp.float32)))
                checked += 1
                min_cos = min(min_cos, cos)
                ok = cos >= COSINE_THRESHOLD
                passed += int(ok)
                if not item.did_backward:
                    continue
                # replay the miner's local update so later items line up
                if role == "last":
                    labels = labels_for[item.sample_key]
                    _, g_params, _ = sm.last_stage_loss_and_grads(
                        params, x_in, labels, spec)
                else:
                    g_out_key = self.transport.schema.gradient_for(
                        item.out_key)
                    if not self.transport.exists(g_out_key):
                        continue
                    g_out = self.transport.get(g_out_key, actor=self.actor)
                    if isinstance(g_out, dict) and g_out.get("codec"):
                        # int8 gradient wire (SwarmConfig.wire_codec):
                        # replay with the same dequantized codes the miner
                        # trained on
                        from repro.core import compression
                        g_out = jnp.reshape(compression.decode(g_out),
                                            g_out["shape"])
                    g_params, _ = sm.stage_backward(params, x_in, g_out,
                                                    spec, role)
                # the miner's own update program, donating the restored
                # copies this replay owns
                params, opt_state, inner_step = miner.update(
                    g_params, opt_state, params, inner_step)
                if ok:
                    validated_backwards += 1.0

            result = ValidationResult(miner.uid, epoch, checked, passed,
                                      validated_backwards, min_cos)
            self.results.append(result)
            self.ledger.record(miner.uid, epoch, result.score, t_now)
            return result

    # ------------------------------------------------------------------
    # sharded-sync reduce audits (§5.2 agreement, from wire artifacts)
    # ------------------------------------------------------------------

    def audit_reduce(self, epoch: int, stage: int) -> ReduceAuditResult:
        """Flag tampering reducers from the store's redundant copies alone:
        every shard has two independent reduced copies, so a deceptive
        reducer disagrees with *all* of its partners (Fig 7a) — visible to
        anyone who can read the store, which is the §5 trustless claim."""
        with span("audit.reduce"):
            uids, agree = butterfly.store_agreement(self.transport, epoch,
                                                    stage, actor=self.actor)
            flagged = []
            for i, uid in enumerate(uids):
                others = agree[i][np.arange(len(uids)) != i]
                if others.size and np.nanmean(others) < 0.5:
                    flagged.append(uid)
            return ReduceAuditResult(epoch, stage, uids, agree, flagged)

    def replay_reduce(self, miner: Miner) -> tuple[int, int, float]:
        """Replay ``miner``'s logged reduce work: recompute each masked
        merge from the same shard uploads and compare (cosine) to the
        reduced copy the miner put on the wire.  Returns (checked, passed,
        min_cosine) — the reduce-work analogue of ``validate_epoch``."""
        checked = passed = 0
        min_cos = 1.0
        for item in miner.reduce_log:
            blocks, valid = [], []
            for key in item.in_keys:
                if not self.transport.exists(key):
                    blocks.append(None)
                    valid.append(False)
                    continue
                payload = self.transport.get(key, actor=self.actor)
                blocks.append(np.asarray(compression.decode(payload)))
                valid.append(True)
            if not any(valid):
                # nothing to recompute from (inputs GC'd or fabricated):
                # the work is unverifiable — score it as failed, don't crash
                checked += 1
                min_cos = -1.0
                continue
            width = next(b.shape[0] for b in blocks if b is not None)
            stacked = np.stack([b if b is not None
                                else np.zeros(width, np.float32)
                                for b in blocks])
            mine = np.asarray(ops.shard_merge(
                jnp.asarray(stacked), jnp.asarray(np.array(valid))))
            theirs = np.asarray(compression.decode(
                self.transport.get(item.out_key, actor=self.actor)))
            cos = float(cosine_similarity(jnp.asarray(mine),
                                          jnp.asarray(theirs)))
            checked += 1
            min_cos = min(min_cos, cos)
            passed += int(cos >= COSINE_THRESHOLD)
        return checked, passed, min_cos
