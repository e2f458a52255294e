"""Phase objects behind the ``Phase`` protocol + the ``EpochDriver``.

The seed ``Orchestrator.run_epoch`` hard-coded the Fig 2 epoch timeline in
one ~180-line method; each stage is now its own object so scenarios can
re-order, replace or extend the timeline (async joins, multi-validator
panels, partition faults) without touching the core loop:

  TrainingPhase    CLASP-sampled pathways, forward/backward over the
                   transport, SWARM rerouting, stragglers
  ValidationPhase  validators replay tracked miners from their sync
                   snapshots (runs *before* merge: replay starts from the
                   pre-merge snapshot, exactly as the seed did)
  SharingPhase     qualifying miners upload codec-compressed weights —
                   dense full vectors, or per-shard payloads when
                   ``SwarmConfig.sync_mode == "sharded"`` (§5.1)
  SyncPhase        butterfly all-reduce + DiLoCo outer step + anchor
                   download for everyone (incl. joiners).  Dense mode
                   reduces centrally in-process (the golden oracle);
                   sharded mode runs the reduce as per-miner
                   store-and-forward actions over the transport
                   (``ButterflyExecutor``), so per-link byte accounting
                   reproduces the §5.3 closed form 4W + 2W/N
  ReduceAuditPhase sharded only: validators rebuild the agreement matrix
                   from the store's redundant reduced copies (trustless
                   tamper detection from wire artifacts alone)

Determinism contract: with ``InProcessTransport`` the default timeline
reproduces the seed trajectory bit-exactly — every RNG draw (pathway
sampling, drop rolls, fault corruption) happens in the same order as the
seed monolith.  Phases that reorder RNG-consuming work define a *different*
scenario, not a bug, but must say so.

``EventDriver`` (the actor runtime, ROADMAP item 1) replaces the lockstep
phase barriers with store-observed completion events: it publishes the
epoch *plan* and the per-tick token/label batches up front, then advances
on watermark keys (tick losses, validator scores, shard/weight uploads)
that concurrently running actor processes publish as they finish.  All
swarm RNG draws happen at plan time in exactly the lockstep order, so the
loss trajectory reproduces the in-process oracle at the same seed.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Iterable, Optional, Protocol, runtime_checkable

import jax
from jax.flatten_util import ravel_pytree
import jax.numpy as jnp
import numpy as np

from repro.api.config import EpochStats
from repro.api.messages import (
    ActivationMsg,
    AnchorMsg,
    EpochPlanMsg,
    GradientMsg,
    LabelsMsg,
    ScoreMsg,
    ServeCodeMsg,
    ServeDoneMsg,
    ServePlanMsg,
    ServeRequestMsg,
    ServeRoundPlanMsg,
    ServeTokenMsg,
    TickLossMsg,
    WeightUploadMsg,
)
from repro.common import span
from repro.core import butterfly, clasp, compression, diloco


@dataclasses.dataclass
class EpochState:
    """Mutable scratchpad one epoch's phases write into; the driver folds
    it into ``EpochStats`` at the end."""
    epoch: int
    snapshots: dict[int, dict]
    records: list = dataclasses.field(default_factory=list)
    labels_for: dict = dataclasses.field(default_factory=dict)
    stalled: int = 0
    validation: list = dataclasses.field(default_factory=list)
    batches: dict[int, int] = dataclasses.field(default_factory=dict)
    merge_quorum: bool = False
    b_eff: int = 0
    # sharing -> sync handoff: stage -> (qualifying miners, decoded uploads)
    qualified: dict[int, list] = dataclasses.field(default_factory=dict)
    uploads: dict[int, dict[int, np.ndarray]] = dataclasses.field(
        default_factory=dict)
    # sharded-sync handoff: stage -> store-and-forward executor (the plan
    # rides on it); dense runs leave this empty
    executors: dict[int, Any] = dataclasses.field(default_factory=dict)
    merged_stages: int = 0
    agreement: dict[int, np.ndarray] = dataclasses.field(default_factory=dict)
    reduce_audits: list = dataclasses.field(default_factory=list)
    # graceful degradation (EventDriver): ticks re-assigned to survivors
    # after an ActorDied, folded into EpochStats.replanned
    replanned: int = 0


@runtime_checkable
class Phase(Protocol):
    """One slice of the epoch timeline.  ``run`` mutates ``state`` (and the
    swarm: miner params, anchors, ledger) through the swarm's transport."""
    name: str

    def run(self, swarm: Any, state: EpochState) -> None: ...


class TrainingPhase:
    name = "training"

    def run(self, swarm, state: EpochState) -> None:
        S = swarm.config
        if getattr(S, "pipeline_virtual_stages", 1) != 1:
            # one miner owns one contiguous stage slice; an interleaved
            # timetable would need each miner to hold V disjoint chunks
            # and the store schema to key activations by chunk, not stage
            raise NotImplementedError(
                "store-path training is stage-granular: "
                "pipeline_virtual_stages > 1 only applies to the on-mesh "
                "engine (repro.core.pipeline / launch.train)")
        tp, schema = swarm.transport, swarm.transport.schema
        for tick in range(S.inner_steps):
            with span("tick"):
                with span("batch"):
                    batch = swarm.corpus.batch(swarm.global_tick)
                swarm.global_tick += 1
                # SWARM routing: sample one available miner per stage,
                # reroute
                pathway = []
                ok = True
                for s in range(S.n_stages):
                    avail = [m for m in swarm.stage_miners(s)
                             if swarm.available(m, tick)]
                    if not avail:
                        ok = False
                        break
                    pathway.append(avail[swarm.rng.randint(len(avail))])
                if not ok:
                    # a whole layer offline: pipeline stall
                    state.stalled += 1
                    continue

                tok_msg = ActivationMsg.tokens(state.epoch, tick)
                with span("batch"):
                    tp.publish(tok_msg, jnp.asarray(batch["tokens"]),
                               actor="orchestrator")
                # ---------------- forward chain ----------------
                in_key = tok_msg.key(schema)
                last_in_key = in_key
                for s, miner in enumerate(pathway):
                    out_msg = ActivationMsg(state.epoch, tick, s, miner.uid)
                    out_key = out_msg.key(schema)
                    if s == S.n_stages - 1:
                        last_in_key = in_key
                    out = miner.forward(tick, in_key, out_key)
                    # an adversarial miner uploads a corrupted activation
                    # in place of its honest output — validators catch the
                    # mismatch on replay, CLASP catches the downstream loss
                    # inflation
                    b = swarm.faults.behavior(miner.uid)
                    if s < S.n_stages - 1 and (b.free_ride
                                               or b.tamper_activations > 0):
                        corrupted = swarm.faults.corrupt_activation(
                            miner.uid, np.asarray(out, np.float32))
                        tp.publish(out_msg,
                                   jnp.asarray(corrupted).astype(out.dtype),
                                   actor=miner.actor)
                    in_key = out_key
                last = pathway[-1]
                labels = jnp.asarray(batch["labels"])
                state.labels_for[last_in_key] = labels

                # ---------------- backward chain ----------------
                loss, g = last.backward_last(last_in_key, labels)
                state.records.append(clasp.PathwayRecord(
                    tuple(m.uid for m in pathway), loss))
                for s in range(S.n_stages - 2, -1, -1):
                    miner = pathway[s]
                    msg = GradientMsg(state.epoch, tick, s, miner.uid)
                    if S.wire_codec == "int8":
                        # the paper's symmetric compression: gradient
                        # hand-offs ship as blockwise-int8 codes (store
                        # bytes and the simulated clock see the real
                        # on-wire size); miners train on the dequantized
                        # codes, and validator replay decodes the same
                        # payload, so both sides see one wire
                        flat = jnp.ravel(jnp.asarray(g, jnp.float32))
                        payload = dict(compression.encode(flat, "int8"),
                                       shape=tuple(np.shape(g)))
                        tp.publish(msg, payload, actor="orchestrator")
                        g = jnp.reshape(
                            compression.decode(payload),
                            np.shape(g)).astype(jnp.asarray(g).dtype)
                    else:
                        tp.publish(msg, g, actor="orchestrator")
                    g = miner.backward(miner.work_log[-1].sample_key, g)


class ValidationPhase:
    """Each validator tracks a random miner (§3: random assignment) and
    publishes its verdict as a ``ScoreMsg`` so emissions are auditable
    from the store alone.  Only snapshotted miners are assignable: an
    async joiner registered mid-epoch has nothing to replay yet and is
    tracked from its first full epoch."""
    name = "validation"

    def run(self, swarm, state: EpochState) -> None:
        t_now = state.epoch * swarm.config.sync_interval_hours
        # a miner registered mid-epoch (async join, §2.2) has no epoch-start
        # snapshot to replay from: it is skipped this epoch and becomes
        # trackable from the next, after its first full sync
        uids = sorted(u for u in swarm.miners if u in state.snapshots)
        if not uids:
            return
        for v in swarm.validators:
            uid = uids[swarm.rng.randint(len(uids))]
            m = swarm.miners[uid]
            res = v.validate_epoch(m, state.snapshots[uid], state.epoch,
                                   t_now, state.labels_for,
                                   max_items=swarm.config.validate_max_items)
            swarm.transport.publish(
                ScoreMsg(state.epoch, v.uid, uid),
                np.asarray([res.score, res.checked, res.passed,
                            res.min_cosine], np.float32),
                actor=v.actor)
            state.validation.append(res)


class SharingPhase:
    """Compressed sharing (§2.1): qualifying miners (B_m >= B_min, quorum)
    upload codec-compressed weight vectors within their layer.

    ``sync_mode="sharded"`` uploads per-shard payloads on the butterfly
    plan's (block-aligned) bounds instead of one dense vector — same bytes
    on the wire, but addressable at shard granularity so the reduce can be
    store-and-forward.  RNG order matches the dense branch (weights read,
    then fault corruption, in qualifying order), so fault-free trajectories
    are unchanged."""
    name = "sharing"

    def run(self, swarm, state: EpochState) -> None:
        S = swarm.config
        state.batches = {m.uid: m.batches_done
                         for m in swarm.miners.values()}
        state.b_eff = diloco.effective_batch(state.batches, S.b_min)
        state.merge_quorum = diloco.should_merge(state.batches, S.b_min,
                                                 S.quorum_frac)
        if not state.merge_quorum:
            return
        for s in range(S.n_stages):
            qual = [m for m in swarm.stage_miners(s)
                    if m.batches_done >= S.b_min]
            if len(qual) < 2:
                continue
            if S.sync_mode == "sharded":
                self._share_sharded(swarm, state, s, qual)
            else:
                self._share_dense(swarm, state, s, qual)

    def _share_dense(self, swarm, state: EpochState, s: int,
                     qual: list) -> None:
        S = swarm.config
        uploads: dict[int, np.ndarray] = {}
        with swarm.transport.parallel():   # distinct links: overlap
            for idx, m in enumerate(qual):
                vec = m.weights_vector()
                vec = swarm.faults.corrupt_weights(m.uid, vec)
                with span("share.upload"):
                    payload = compression.encode(jnp.asarray(vec),
                                                 S.share_codec)
                    swarm.transport.publish(
                        WeightUploadMsg(state.epoch, s, m.uid,
                                        codec=S.share_codec),
                        payload, actor=m.actor)
                    uploads[idx] = np.asarray(
                        compression.decode(payload, vec.shape[0]))
        state.qualified[s] = qual
        state.uploads[s] = uploads

    def _share_sharded(self, swarm, state: EpochState, s: int,
                       qual: list) -> None:
        S = swarm.config
        assert S.share_codec in compression.SLICEABLE_CODECS, \
            f"share_codec {S.share_codec!r} cannot shard losslessly"
        vec0 = qual[0].weights_vector()
        align = compression.INT8_BLOCK if S.share_codec == "int8" else 1
        plan = butterfly.make_plan(len(qual), int(vec0.shape[0]),
                                   seed=S.seed + state.epoch * 131 + s,
                                   align=align)
        ex = butterfly.ButterflyExecutor(
            plan, swarm.transport, epoch=state.epoch, stage=s,
            uids=[m.uid for m in qual], codec=S.share_codec)
        with swarm.transport.parallel():   # distinct links: overlap
            for idx, m in enumerate(qual):
                vec = vec0 if idx == 0 else m.weights_vector()
                vec = swarm.faults.corrupt_weights(m.uid, vec)
                ex.upload_vector(idx, vec, actor=m.actor)
        state.qualified[s] = qual
        state.executors[s] = ex


class SyncPhase:
    """Butterfly all-reduce per layer (agreement matrix exposes tamperers),
    DiLoCo outer Nesterov step on the per-stage anchor, then everyone —
    stragglers and joiners included — downloads the anchor.

    Dense mode reduces the decoded uploads centrally in-process (the
    golden oracle).  Sharded mode executes the same reduce as per-miner
    store-and-forward actions: each qualifying miner downloads all N
    copies of its assigned shards, masked-merges them and re-uploads its
    reduced copy — then the anchor is assembled from the redundant copies
    in the store.  Anchors match the dense oracle to float equality
    (block-aligned shard codes), and per-miner link bytes reproduce the
    §5.3 closed form 4W + 2W/N."""
    name = "sync"

    def run(self, swarm, state: EpochState) -> None:
        if not state.merge_quorum:
            return
        for s, qual in state.qualified.items():
            if s in state.executors:
                merged = self._reduce_sharded(swarm, state, s, qual)
            else:
                merged = self._reduce_dense(swarm, state, s, qual)
            self._outer_step_and_full_sync(swarm, state, s, merged)

    def _reduce_dense(self, swarm, state: EpochState, s: int,
                      qual: list) -> np.ndarray:
        S = swarm.config
        uploads = state.uploads[s]
        plan = butterfly.make_plan(len(qual), uploads[0].shape[0],
                                   seed=S.seed + state.epoch * 131 + s)
        # a weight-tampering miner also reduces dishonestly: its merged
        # shard copies deviate, which is what the agreement matrix
        # exposes (paper Fig 7a)
        tamper = {idx: swarm.faults.behavior(m.uid).tamper_weights
                  for idx, m in enumerate(qual)
                  if swarm.faults.behavior(m.uid).tamper_weights > 0}
        with span("sync.reduce"):
            copies = butterfly.reduce_with_copies(plan, uploads,
                                                  tamper=tamper or None)
            state.agreement[s] = butterfly.agreement_matrix(plan, copies)
            merged, _, _ = butterfly.reduce_shards(plan, uploads)
        return merged

    def _reduce_sharded(self, swarm, state: EpochState, s: int,
                        qual: list) -> np.ndarray:
        ex = state.executors[s]
        # every reducer's download->merge->re-upload rides its own link;
        # distinct links overlap on the simulated clock
        with swarm.transport.parallel():
            for idx, m in enumerate(qual):
                tamper = swarm.faults.behavior(m.uid).tamper_weights
                m.run_reduce(ex, idx, tamper=tamper if tamper > 0 else 0.0)
        merged, _, _ = ex.collect(actor="orchestrator")
        state.agreement[s] = ex.last_agreement   # computed inside collect
        return merged

    def _outer_step_and_full_sync(self, swarm, state: EpochState, s: int,
                                  merged: np.ndarray) -> None:
        S = swarm.config
        # --- DiLoCo outer step on the per-stage anchor ---
        with span("sync.outer_step"):
            _, unravel = ravel_pytree(
                jax.tree.map(lambda x: x.astype(jnp.float32),
                             swarm.anchors[s]))
            avg = unravel(jnp.asarray(merged))
            swarm.outer[s] = diloco.outer_update(
                swarm.outer[s], avg, outer_lr=S.outer_lr,
                outer_momentum=S.outer_momentum)
            swarm.anchors[s] = jax.tree.map(
                lambda a, p: a.astype(p.dtype), swarm.outer[s].anchor,
                swarm.anchors[s])
        # --- full sync: every miner (incl. stragglers/joiners) downloads
        msg = AnchorMsg(state.epoch, s)
        with span("sync.anchor_publish"):
            anchor_vec, _ = ravel_pytree(
                jax.tree.map(lambda x: x.astype(jnp.float32),
                             swarm.anchors[s]))
            swarm.transport.publish(msg, np.asarray(anchor_vec),
                                    actor="orchestrator")
        with swarm.transport.parallel():
            for m in swarm.stage_miners(s):
                vec = swarm.transport.fetch(msg, actor=m.actor)
                m.load_weights_vector(vec)
        state.merged_stages += 1


class ReduceAuditPhase:
    """Sharded-sync audit (runs after the merge): each validator rebuilds
    the shard agreement matrix from the store's redundant reduced copies —
    tampering reducers are flagged from wire artifacts alone, no miner
    state or plan reconstruction needed (§5.2, Fig 7a)."""
    name = "reduce_audit"

    def run(self, swarm, state: EpochState) -> None:
        for s in sorted(state.executors):
            for v in swarm.validators:
                state.reduce_audits.append(
                    v.audit_reduce(state.epoch, s))


class OverlappedTrainingSharing:  # swarmlint: implements=Phase
    """Async-phases scenario (ROADMAP open item): qualifying miners upload
    their compressed weights *while* training-tick activations still stream,
    inside one ``transport.parallel()`` block.

    Clock-model honesty: ``parallel()`` overlaps transfers across *links*
    only — a miner's own weight upload still serializes with its own
    activation hand-offs on its link, so what the scenario hides is
    idle-link time (uploads ride links whose miners are waiting for their
    next tick).  Within the block the causally-sequential cross-link
    activation chain is also overlapped, so the saved seconds reported by
    bench_swarm are an upper bound on the true overlap win.  RNG order equals
    the default timeline's (sharing draws no swarm RNG), so the trajectory
    is unchanged for fault-free swarms — bench_swarm asserts equal loss.
    """
    name = "training+sharing"

    def __init__(self):
        self.training = TrainingPhase()
        self.sharing = SharingPhase()

    def run(self, swarm, state: EpochState) -> None:
        with swarm.transport.parallel():
            self.training.run(swarm, state)
            self.sharing.run(swarm, state)


def default_phases() -> list[Phase]:
    """Seed-equivalent timeline.  Validation precedes merge because replay
    starts from the epoch-start snapshot (the miner's last full sync)."""
    return [TrainingPhase(), ValidationPhase(), SharingPhase(), SyncPhase()]


def overlapped_phases() -> list[Phase]:
    """Async scenario: training + sharing overlap on the simulated clock;
    validation still precedes the merge (SyncPhase applies the uploads)."""
    return [OverlappedTrainingSharing(), ValidationPhase(), SyncPhase()]


def sharded_phases() -> list[Phase]:
    """Store-and-forward timeline (``sync_mode="sharded"``): the default
    timeline plus the post-merge store-side reduce audit.  Sharing/Sync
    branch on the config, so the phase objects themselves are the same."""
    return [TrainingPhase(), ValidationPhase(), SharingPhase(), SyncPhase(),
            ReduceAuditPhase()]


def revise_plan(plan: dict, done_ticks: set, dead_uid: int,
                survivor: Optional[int], gradient_missing) -> tuple:
    """Pure re-planning after a miner death — the graceful-degradation
    core, kept free of transports/processes so it unit-tests in isolation.

    For every tick the dead miner participates in:

      * loss already published (``done_ticks``) — the tick stands as
        trained; if the dead miner's *backward* hand-off never landed
        (``gradient_missing``), the tick is **orphaned**: miners blocked
        on its broken gradient chain abandon that backward;
      * loss pending — the dead slot is substituted with ``survivor``
        (the survivor redoes the stage forward from the still-stored
        upstream activation), or the tick is **dropped** when the stage
        has no survivor (counts as stalled, like an all-offline layer).

    ``qualified`` is **fixed at plan time** — a revision never rewrites
    the merge layout, because actors may already be mid-reduce against
    it (different actors folding different layouts would shard against
    different butterfly plans).  The driver masks dead participants at
    reduce time instead: dense averages the uploads that arrived,
    sharded fails over to the surviving redundant copy.  ``tracked`` is
    kept — the validator publishes a partial score over what it already
    checked (the ``dead`` list tells it to stop).  Returns
    ``(revision, n_replanned, orphaned, dropped)``.
    """
    stage = plan["stage_of"][dead_uid]
    ticks: list = []
    orphaned: list = []
    dropped: list = []
    n_replanned = 0
    for t, uids in plan["ticks"]:
        uids = tuple(uids)
        if uids[stage] != dead_uid:
            ticks.append((t, uids))
            continue
        if t in done_ticks:
            ticks.append((t, uids))
            if stage > 0 and gradient_missing(t, uids):
                orphaned.append(t)
            continue
        if survivor is None:
            dropped.append(t)
            continue
        ticks.append((t, uids[:stage] + (survivor,) + uids[stage + 1:]))
        n_replanned += 1
    revision = dict(
        plan,
        ticks=tuple(ticks),
        orphaned=tuple(sorted(set(plan.get("orphaned", ())) | set(orphaned))),
        dropped=tuple(sorted(set(plan.get("dropped", ())) | set(dropped))),
        dead=tuple(sorted(set(plan.get("dead", ())) | {dead_uid})),
    )
    return revision, n_replanned, orphaned, dropped


class EpochDriver:
    """Runs the phase list over a swarm and folds the scratchpad into
    ``EpochStats``.  Swap/extend ``phases`` to define new scenarios."""

    def __init__(self, phases: Optional[Iterable[Phase]] = None):
        self.phases: list[Phase] = list(phases or default_phases())
        self._gc_floor = 0          # first epoch whose weights/scores remain
        # retention pins (docs/CHAOS.md): tag -> epoch.  GC never advances
        # past the lowest pin, so the weight/score/control keys a
        # crash-resume replay still needs survive even when
        # ``retain_epochs`` is smaller than the resume distance
        self._pins: dict[str, int] = {}

    def pin_retention(self, tag: str, epoch: int) -> None:
        """Hold every GC floor at or below ``epoch`` until released —
        called with a respawning actor's snapshot epoch so its forward
        replay finds the anchors/plans it needs."""
        self._pins[tag] = min(int(epoch), self._pins.get(tag, int(epoch)))

    def release_retention(self, tag: str) -> None:
        self._pins.pop(tag, None)

    def _pin_floor(self) -> Optional[int]:
        return min(self._pins.values()) if self._pins else None

    def run_epoch(self, swarm) -> EpochStats:
        with span("epoch"):
            for m in swarm.miners.values():
                m.reset_epoch()
            state = EpochState(
                epoch=swarm.epoch,
                snapshots={uid: m.snapshot()
                           for uid, m in swarm.miners.items()})
            for phase in self.phases:
                with span("phase." + phase.name):
                    phase.run(swarm, state)
            return self._finalize(swarm, state)

    def _finalize(self, swarm, state: EpochState) -> EpochStats:
        """Fold the epoch scratchpad into ``EpochStats`` and GC the store —
        shared by the lockstep and event-driven timelines."""
        with span("finalize"):
            if not state.batches:
                # a timeline without SharingPhase still reports the batch
                # census
                state.batches = {m.uid: m.batches_done
                                 for m in swarm.miners.values()}
                state.b_eff = diloco.effective_batch(state.batches,
                                                     swarm.config.b_min)

            n_miners = len(swarm.miners)
            layer_of = np.array([swarm.miners[u].stage
                                 for u in sorted(swarm.miners.keys())])
            report = (clasp.attribute(state.records, n_miners, layer_of)
                      if state.records else None)
            t_now = swarm.epoch * swarm.config.sync_interval_hours
            swarm.ledger.prune(t_now)
            emissions = swarm.ledger.emissions(
                t_now, miners=sorted(swarm.miners.keys()))

            stats = EpochStats(
                epoch=swarm.epoch,
                mean_loss=float(np.mean([r.loss for r in state.records]))
                if state.records else float("nan"),
                b_eff=state.b_eff,
                batches=dict(state.batches),
                merged_stages=state.merged_stages,
                stalled_ticks=state.stalled,
                agreement=state.agreement,
                clasp=report,
                validation=state.validation,
                emissions=emissions,
                reduce_audits=state.reduce_audits,
                replanned_ticks=state.replanned,
            )
            swarm.history.append(stats)
            swarm.epoch += 1
            # activations from this epoch are garbage-collected from the
            # store
            schema = swarm.transport.schema
            swarm.transport.delete_prefix(
                schema.activations_prefix(stats.epoch))
            # weight/score planes: retention-window GC.  The seed
            # behaviour (keep everything, for replay/audit) is
            # retain_epochs=None; with a window of K, only the last K
            # epochs' weights/ and scores/ survive — long runs no longer
            # grow the store without bound
            retain = swarm.config.retain_epochs
            if retain is not None:
                pin = self._pin_floor()
                while self._gc_floor <= stats.epoch - retain \
                        and (pin is None or self._gc_floor < pin):
                    e = self._gc_floor
                    swarm.transport.delete_prefix(schema.weights_prefix(e))
                    swarm.transport.delete_prefix(schema.scores_prefix(e))
                    self._gc_floor += 1
            return stats


class EventDriver(EpochDriver):
    """Event-driven epoch timeline for the concurrent actor runtime.

    Where ``EpochDriver`` *calls* miners and validators in lockstep, this
    driver never touches their compute: it publishes the epoch plan (the
    deterministic schedule every actor derives its work list from), the
    token/label batches, and then advances on watermark keys the actor
    processes publish — tick losses from last-stage miners, scores from
    validators, weight/shard uploads from qualifying miners.  The driver
    keeps only the genuinely central work: plan-time RNG, the dense
    golden-oracle reduce (or sharded anchor assembly), the DiLoCo outer
    step, the ledger, and store GC.

    Determinism: every swarm RNG draw (per-tick availability rolls +
    pathway sampling, then validator assignment) happens at plan time in
    exactly the lockstep order, and actors interact only through
    bit-exact store payloads, so dense and sharded runs reproduce the
    in-process loss trajectory at the same seed.  Fault behaviors that
    corrupt *payloads* (tamper, free-ride) are driver-side in the
    lockstep timeline and are rejected by ``ActorSwarm``; drop/straggle
    are schedule-only and fully supported.

    ``swarm.check_liveness`` (when present) is consulted while polling so
    a crashed actor surfaces as ``ActorDied`` instead of a timeout.

    Graceful degradation (docs/CHAOS.md): with a KeySchema v4 transport
    an ``ActorDied`` mid-epoch is survivable — the driver re-plans the
    dead miner's remaining ticks onto a stage survivor and publishes the
    revision under ``control/ep{E}/plan/r{R}`` (actors poll for it while
    blocked); a dead validator just forfeits its score; a reducer lost
    during the sharded merge fails over to the surviving redundant
    copy's partner (the §5.2 redundancy — honest copies are
    bit-identical, so the anchor stays bit-exact).
    """

    failover_grace = 5.0     # partner-copy patience once one copy landed

    def __init__(self, poll_interval: float = 0.002, timeout: float = 120.0):
        super().__init__()
        self.phases = []            # the timeline is event-driven, not phased
        self.poll_interval = poll_interval
        self.timeout = timeout
        self._ctl_floor = 0         # first epoch whose control keys remain
        self._plan: dict = {}       # latest plan (incl. revisions) in flight
        self._plan_rev = 0
        self._dead_validators: set = set()

    # -- store polling ---------------------------------------------------

    def _await(self, swarm, key: str,
               timeout: Optional[float] = None) -> None:
        tp = swarm.transport
        check = getattr(swarm, "check_liveness", None)
        wait_for = getattr(tp, "wait_for", None)
        budget = self.timeout if timeout is None else timeout
        deadline = time.monotonic() + budget
        polls = 0
        while True:
            if check is not None and polls % 25 == 0:
                check()
            if wait_for is not None:
                # park server-side (zero CPU) in bounded slices so the
                # liveness check still runs between them
                slice_s = min(0.25, max(budget, 0.01))
                if wait_for(key, timeout=slice_s, actor="orchestrator"):
                    return
                polls += 25          # one slice ~ a liveness interval
            else:
                if tp.exists(key):
                    return
                time.sleep(self.poll_interval)
                polls += 1
            if time.monotonic() >= deadline:
                raise TimeoutError(
                    f"event driver timed out after {budget}s "
                    f"awaiting {key!r}")

    # -- graceful degradation --------------------------------------------

    @staticmethod
    def _death_of(err: Exception) -> Optional[str]:
        """Duck-typed ``ActorDied`` detection (the actor module imports
        this one; importing it back at module level would be circular)."""
        name = getattr(err, "actor", None)
        return name if isinstance(err, RuntimeError) and name else None

    def _handle_actor_death(self, swarm, state: EpochState,
                            err: Exception) -> None:
        """Re-plan around a dead actor instead of aborting the epoch.

        Dead validator: forget it, forfeit its score.  Dead miner:
        compute a :func:`revise_plan` revision from the store's tick-loss
        watermarks, publish it under ``plan_rev`` for blocked actors, and
        rewrite the driver's own tick table.  Raises the original error
        when the transport cannot carry revisions (schema < v4)."""
        name = self._death_of(err)
        supervisor = getattr(swarm, "supervisor", None)
        if supervisor is not None:
            supervisor.forget(name)
        if not name.startswith("miner"):
            self._dead_validators.add(name)
            return
        uid = int(name[len("miner"):])
        dead_uids = getattr(swarm, "dead_uids", None)
        if dead_uids is not None:
            dead_uids.add(uid)
        tp, schema = swarm.transport, swarm.transport.schema
        if schema.version < 4:
            raise err            # no revision channel: fail loudly
        plan = self._plan
        if uid in plan.get("dead", ()):
            return               # already re-planned around this miner
        epoch = state.epoch
        done = {t for t, _u, _g in self._ticks
                if tp.exists(schema.tick_loss(epoch, t))}
        stage = plan["stage_of"][uid]
        known_dead = set(plan.get("dead", ())) | {uid}
        alive = sorted(u for u, st in plan["stage_of"].items()
                       if st == stage and u not in known_dead)
        survivor = alive[0] if alive else None
        revision, n_replanned, _orphaned, dropped = revise_plan(
            plan, done, uid, survivor,
            gradient_missing=lambda t, uids: not tp.exists(
                schema.gradient(epoch, t, stage - 1, uids[stage - 1])))
        self._plan_rev += 1
        revision["rev"] = self._plan_rev
        tp.put(schema.plan_rev(epoch, self._plan_rev), revision,
               actor="orchestrator")
        self._plan = revision
        state.replanned += n_replanned
        # rewrite the driver's tick table: substituted pathways keep their
        # slot (the survivor's loss arrives under the same tick key),
        # dropped ticks leave the await loop as stalled
        by_tick = {t: tuple(uids) for t, uids in revision["ticks"]}
        new_ticks = []
        for t, _uids, gt in self._ticks:
            if t in dropped:
                state.stalled += 1
                continue
            new_ticks.append((t, by_tick[t], gt))
        self._ticks = new_ticks

    # -- the timeline ----------------------------------------------------

    def run_epoch(self, swarm) -> EpochStats:
        S = swarm.config
        tp, schema = swarm.transport, swarm.transport.schema
        if schema.version < 3:
            raise ValueError(
                "EventDriver needs a KeySchema v3 transport (control-plane "
                f"keys); got v{schema.version}")
        epoch = swarm.epoch
        for m in swarm.miners.values():
            m.reset_epoch()             # parent-side handles: census hygiene
        state = EpochState(epoch=epoch, snapshots={})

        plan = self._build_plan(swarm, state)
        self._plan = plan
        self._plan_rev = 0
        tp.publish(EpochPlanMsg(epoch), plan, actor="orchestrator")
        for tick, _uids, gt in self._ticks:
            batch = swarm.corpus.batch(gt)
            tp.publish(ActivationMsg.tokens(epoch, tick),
                       jnp.asarray(batch["tokens"]), actor="orchestrator")
            tp.publish(LabelsMsg(epoch, tick),
                       jnp.asarray(batch["labels"]), actor="orchestrator")

        # training watermarks: fold tick losses into PathwayRecords in tick
        # order (actors may publish out of order; the records must not).
        # An ActorDied surfaced by the liveness hook re-plans and retries
        # the same slot — self._ticks may shrink (dropped) or be rewritten
        # (survivor substitution) under us
        i = 0
        while i < len(self._ticks):
            tick, uids, _gt = self._ticks[i]
            key = TickLossMsg(epoch, tick).key(schema)
            try:
                self._await(swarm, key)
            except RuntimeError as err:
                if self._death_of(err) is None:
                    raise
                self._handle_actor_death(swarm, state, err)
                continue
            state.records.append(clasp.PathwayRecord(
                self._ticks[i][1],
                float(tp.get(key, actor="orchestrator"))))
            i += 1

        self._collect_scores(swarm, state, self._plan)

        if state.merge_quorum:
            for s in sorted(self._plan["qualified"]):
                quids = tuple(self._plan["qualified"][s])
                while True:
                    try:
                        if S.sync_mode == "sharded":
                            merged = self._reduce_sharded(swarm, state, s,
                                                          quids)
                        else:
                            merged = self._reduce_dense(swarm, state, s,
                                                        quids)
                    except RuntimeError as err:
                        if self._death_of(err) is None:
                            raise
                        self._handle_actor_death(swarm, state, err)
                        continue     # retry: dead uploads are now masked
                    if merged is None:
                        # every qualifier died pre-upload: republish the
                        # unchanged anchor so survivors parked on the
                        # full-sync download still unblock
                        anchor_vec, _ = ravel_pytree(jax.tree.map(
                            lambda x: x.astype(jnp.float32),
                            swarm.anchors[s]))
                        swarm.transport.publish(
                            AnchorMsg(state.epoch, s),
                            np.asarray(anchor_vec), actor="orchestrator")
                    else:
                        self._outer_step_and_publish(swarm, state, s,
                                                     merged)
                    break
            for s in sorted(state.executors):
                for v in swarm.validators:
                    state.reduce_audits.append(v.audit_reduce(epoch, s))

        stats = self._finalize(swarm, state)
        # control-plane GC is a pinned floor like the weight/score planes:
        # a crash-resume replay needs the plans/revisions back to its
        # snapshot epoch, so respawns pin the floor (pin_retention) and
        # the sweep stops there until released
        pin = self._pin_floor()
        limit = stats.epoch + 1
        if pin is not None:
            limit = min(limit, pin)
        while self._ctl_floor < limit:
            tp.delete_prefix(schema.control_prefix(self._ctl_floor))
            self._ctl_floor += 1
        return stats

    # -- plan construction (all swarm RNG, lockstep order) ---------------

    def _build_plan(self, swarm, state: EpochState) -> dict:
        S = swarm.config
        # miners that died in earlier epochs and have not respawned are
        # not schedulable; the availability roll still happens for them so
        # the RNG stream (and the no-death trajectory) is unchanged
        dead = getattr(swarm, "dead_uids", None) or set()
        ticks = []
        for tick in range(S.inner_steps):
            gt = swarm.global_tick      # the batch index, like the lockstep
            swarm.global_tick += 1      # driver consumes it even when stalled
            pathway = []
            ok = True
            for s in range(S.n_stages):
                avail = [m for m in swarm.stage_miners(s)
                         if swarm.available(m, tick)
                         and m.uid not in dead]
                if not avail:
                    ok = False
                    break
                pathway.append(avail[swarm.rng.randint(len(avail))].uid)
            if not ok:
                state.stalled += 1
                continue
            ticks.append((tick, tuple(pathway), gt))
        self._ticks = ticks

        batches = {uid: 0 for uid in swarm.miners}
        for _tick, uids, _gt in ticks:
            for uid in uids:
                batches[uid] += 1
        state.batches = batches
        state.b_eff = diloco.effective_batch(batches, S.b_min)
        state.merge_quorum = diloco.should_merge(batches, S.b_min,
                                                 S.quorum_frac)
        qualified: dict[int, tuple] = {}
        if state.merge_quorum:
            for s in range(S.n_stages):
                qual = tuple(m.uid for m in swarm.stage_miners(s)
                             if batches[m.uid] >= S.b_min)
                if len(qual) >= 2:
                    qualified[s] = qual

        # validator assignment draws come after every training draw —
        # identical RNG order to the lockstep ValidationPhase
        uids_sorted = sorted(swarm.miners)
        alive_sorted = [u for u in uids_sorted if u not in dead]
        tracked = {}
        if uids_sorted:
            for v in swarm.validators:
                # draw over the full census (RNG parity), then remap a
                # dead pick to a live miner — a validator must never be
                # assigned a peer that cannot publish a snapshot
                uid = uids_sorted[swarm.rng.randint(len(uids_sorted))]
                if uid in dead:
                    if not alive_sorted:
                        continue
                    uid = alive_sorted[uid % len(alive_sorted)]
                tracked[v.uid] = uid

        return {
            "stop": False,
            "epoch": state.epoch,
            "ticks": tuple((t, uids) for t, uids, _gt in ticks),
            "merge": state.merge_quorum,
            "qualified": qualified,
            "tracked": tracked,
            "stage_of": {uid: swarm.miners[uid].stage
                         for uid in uids_sorted},
        }

    # -- validation watermarks -------------------------------------------

    def _collect_scores(self, swarm, state: EpochState, plan: dict) -> None:
        from repro.runtime.validator import ValidationResult
        schema = swarm.transport.schema
        t_now = state.epoch * swarm.config.sync_interval_hours
        for v in swarm.validators:
            uid = plan["tracked"].get(v.uid)
            if uid is None:
                continue
            msg = ScoreMsg(state.epoch, v.uid, uid)
            while True:
                if f"validator{v.uid}" in self._dead_validators:
                    break            # died mid-replay: score forfeited
                try:
                    self._await(swarm, msg.key(schema))
                except RuntimeError as err:
                    if self._death_of(err) is None:
                        raise
                    # a death elsewhere in the fleet: re-plan (the
                    # validator publishes a partial score if its tracked
                    # miner is the casualty) and keep waiting
                    self._handle_actor_death(swarm, state, err)
                    continue
                vec = np.asarray(swarm.transport.fetch(
                    msg, actor="orchestrator"))
                res = ValidationResult(uid, state.epoch, int(vec[1]),
                                       int(vec[2]), float(vec[0]),
                                       float(vec[3]))
                v.results.append(res)
                swarm.ledger.record(uid, state.epoch, res.score, t_now)
                state.validation.append(res)
                break

    # -- merge: await uploads, reduce, outer step, publish anchor --------

    def _stage_vec_len(self, swarm, s: int) -> int:
        vec, _ = ravel_pytree(
            jax.tree.map(lambda x: x.astype(jnp.float32), swarm.anchors[s]))
        return int(vec.shape[0])

    def _reduce_dense(self, swarm, state: EpochState, s: int,
                      quids: tuple) -> Optional[np.ndarray]:
        S = swarm.config
        schema = swarm.transport.schema
        vec_len = self._stage_vec_len(swarm, s)
        # the merge layout is fixed at plan time (revise_plan never
        # rewrites ``qualified``): a dead qualifier is *masked*, not
        # relaid — its upload is used if it landed before the crash,
        # skipped otherwise, and the butterfly's masked mean averages
        # whatever arrived
        dead = set(self._plan.get("dead", ()))
        uploads: dict[int, np.ndarray] = {}
        for idx, uid in enumerate(quids):
            msg = WeightUploadMsg(state.epoch, s, uid, codec=S.share_codec)
            key = msg.key(schema)
            if uid in dead and not swarm.transport.exists(key):
                continue
            self._await(swarm, key)
            payload = swarm.transport.fetch(msg, actor="orchestrator")
            uploads[idx] = np.asarray(compression.decode(payload, vec_len))
        if not uploads:
            return None          # every qualifier died before uploading
        plan = butterfly.make_plan(len(quids), vec_len,
                                   seed=S.seed + state.epoch * 131 + s)
        copies = butterfly.reduce_with_copies(plan, uploads)
        state.agreement[s] = butterfly.agreement_matrix(plan, copies)
        merged, _, _ = butterfly.reduce_shards(plan, uploads)
        return merged

    def _reduce_sharded(self, swarm, state: EpochState, s: int,
                        quids: tuple) -> np.ndarray:
        S = swarm.config
        tp = swarm.transport
        vec_len = self._stage_vec_len(swarm, s)
        align = compression.INT8_BLOCK if S.share_codec == "int8" else 1
        plan = butterfly.make_plan(len(quids), vec_len,
                                   seed=S.seed + state.epoch * 131 + s,
                                   align=align)
        ex = butterfly.ButterflyExecutor(
            plan, swarm.transport, epoch=state.epoch, stage=s,
            uids=list(quids), codec=S.share_codec)
        # reducer failover (§5.2 redundancy): each shard has two
        # independent reduced copies.  The first copy gets the full
        # timeout; once one landed, its partner only gets a short grace —
        # a reducer lost to a crash or a dropped put costs seconds, not
        # the epoch.  Honest copies are bit-identical, so collect()
        # assembling from the survivor keeps the anchor bit-exact.
        # a reducer that died back in the tick phase is already in the
        # plan's dead list — seed the failover set so its never-coming
        # copy gets an exists-check, not a full-timeout await
        dead_idx: set = {quids.index(u)
                         for u in self._plan.get("dead", ())
                         if u in quids}
        for shard, (i, j) in enumerate(plan.pairs):
            lo, hi = plan.shard_bounds(shard)
            if hi == lo:
                continue
            have = 0
            for r in (i, j):
                key = ex.reduced_key(shard, r)
                if r in dead_idx:
                    have += int(tp.exists(key))   # published before dying?
                    continue
                try:
                    self._await(swarm, key,
                                timeout=self.failover_grace if have
                                else None)
                    have += 1
                except TimeoutError:
                    if have == 0:
                        raise        # neither copy: the merge is truly stuck
                    # partner never arrived: fail over to the copy we have
                except RuntimeError as err:
                    name = self._death_of(err)
                    if name is None:
                        raise
                    self._handle_actor_death(swarm, state, err)
                    if name.startswith("miner"):
                        uid = int(name[len("miner"):])
                        if uid in quids:
                            dead_idx.add(quids.index(uid))
                    have += int(tp.exists(key))
            if have == 0:
                raise TimeoutError(
                    f"both reduced copies of stage {s} shard {shard} are "
                    f"lost (reducers {i} and {j}): cannot assemble anchor")
        merged, _, _ = ex.collect(actor="orchestrator")
        state.agreement[s] = ex.last_agreement
        state.executors[s] = ex
        return merged

    def _outer_step_and_publish(self, swarm, state: EpochState, s: int,
                                merged: np.ndarray) -> None:
        S = swarm.config
        _, unravel = ravel_pytree(
            jax.tree.map(lambda x: x.astype(jnp.float32), swarm.anchors[s]))
        avg = unravel(jnp.asarray(merged))
        swarm.outer[s] = diloco.outer_update(
            swarm.outer[s], avg, outer_lr=S.outer_lr,
            outer_momentum=S.outer_momentum)
        swarm.anchors[s] = jax.tree.map(
            lambda a, p: a.astype(p.dtype), swarm.outer[s].anchor,
            swarm.anchors[s])
        anchor_vec, _ = ravel_pytree(
            jax.tree.map(lambda x: x.astype(jnp.float32), swarm.anchors[s]))
        # actors download the anchor themselves (the plan tells them which
        # stages merge); the driver only publishes it
        swarm.transport.publish(AnchorMsg(state.epoch, s),
                                np.asarray(anchor_vec), actor="orchestrator")
        state.merged_stages += 1


# ---------------------------------------------------------------------------
# Serve plane: inference as a pipeline workload (docs/SERVE.md)
# ---------------------------------------------------------------------------
#
# The decode timetable (``compile_timetable("decode", P, n_lanes)``) is the
# single source of execution order: micro-batch slots are *request lanes*,
# and one "round" advances every active lane by one token.  The driver does
# continuous batching — it admits queued requests into free lanes and
# retires finished ones strictly *between* rounds, publishing one lane plan
# per round, so the per-slot stage work (and any jitted callable behind it)
# never changes shape and never recompiles.  Stage compute is a
# ``StageServer`` (one per stage): in-process and socket runs call them
# synchronously in timetable slot order; ``runtime="actors"`` fleets run
# the identical object inside ``ServeActor`` processes driven by the same
# round plans.  Sampling stays in the driver, so stage actors are pure
# deterministic functions of store payloads and greedy decode is
# token-for-token reproducible against the sequential oracle.


@dataclasses.dataclass
class ServeRequest:
    """One inference request: a prompt plus sampling parameters.

    ``arrival_round`` is the earliest decode round the scheduler may admit
    it (0 = available immediately) — tests use it to stagger mid-flight
    admissions deterministically."""
    req: int
    prompt: Any                  # (S,) int token ids (list or array)
    max_new: int = 16
    temperature: float = 0.0
    arrival_round: int = 0


@dataclasses.dataclass
class RequestRecord:
    """Per-request serving record: emitted tokens + latency breakdown."""
    req: int
    tokens: list = dataclasses.field(default_factory=list)
    submit_s: float = 0.0
    first_token_s: Optional[float] = None     # TTFT (prefill + first sample)
    done_s: Optional[float] = None
    token_s: list = dataclasses.field(default_factory=list)  # per-token stamps

    @property
    def ttft(self) -> Optional[float]:
        if self.first_token_s is None:
            return None
        return self.first_token_s - self.submit_s

    @property
    def total(self) -> Optional[float]:
        if self.done_s is None:
            return None
        return self.done_s - self.submit_s


def _serve_await(tp, key: str, *, actor: str, timeout: float = 120.0,
                 poll: float = 0.002):
    """Blocking store read for the serve plane: server-side park when the
    transport supports it (SocketTransport ``wait_for``), polling
    otherwise."""
    wait_for = getattr(tp, "wait_for", None)
    deadline = time.monotonic() + timeout
    while not tp.exists(key):
        if time.monotonic() > deadline:
            raise TimeoutError(f"serve: timed out awaiting {key!r}")
        if wait_for is not None:
            wait_for(key, timeout=0.25, actor=actor)
        else:
            time.sleep(poll)
    return tp.get(key, actor=actor)


class StageServer:
    """One stage's serve-side worker: a ``StageProgram`` + params + one
    stage-local KV cache per request lane.

    ``process_slot`` executes one (round, lane) timetable cell: fetch the
    stage input from the store (prompt tokens / last sampled token on the
    first stage, the upstream boundary code elsewhere), advance the lane's
    cache through the slice, publish the boundary output.  Identical code
    runs in-process under the ``ServeDriver`` and inside ``ServeActor``
    processes — the store payloads are the only interface, so every
    transport serves bit-identical tokens."""

    def __init__(self, spec, stage: int, params, *, n_lanes: int,
                 max_len: int, wire_codec: str = "none"):
        from repro.runtime import stage_model as sm
        self.program = sm.StageProgram(spec, stage, wire_codec)
        self.stage = stage
        self.params = params
        self.n_lanes = n_lanes
        self.max_len = max_len
        self.caches = [self.program.init_cache(1, max_len)
                       for _ in range(n_lanes)]
        self.slots_done = 0

    @property
    def actor(self) -> str:
        return f"server{self.stage}"

    def reset_lane(self, lane: int) -> None:
        """Admission: the lane's cache restarts from length 0 — lanes are
        independent batch rows, so this cannot perturb other lanes."""
        self.caches[lane] = self.program.init_cache(1, self.max_len)

    def process_slot(self, tp, schema, round_: int, entry: dict) -> None:
        lane, req = int(entry["lane"]), int(entry["req"])
        prefill = entry["phase"] == "prefill"
        if self.stage == 0:
            if prefill:
                env = _serve_await(tp, schema.serve_request(req),
                                   actor=self.actor)
                x = jnp.asarray(env["tokens"], jnp.int32)
            else:
                tok = _serve_await(
                    tp, schema.serve_token(req, int(entry["in_index"])),
                    actor=self.actor)
                x = jnp.asarray(tok, jnp.int32).reshape(1, 1)
        else:
            payload = _serve_await(
                tp, schema.serve_code(round_, lane, self.stage - 1),
                actor=self.actor)
            x = self.program.decode_wire(payload)
        if prefill:
            self.reset_lane(lane)
        out, self.caches[lane] = self.program.decode_step(
            self.params, x, self.caches[lane])
        if self.program.role in ("last", "solo"):
            # ship only the last position's logits: that is all sampling
            # needs, and it keeps the serve plane's store traffic O(vocab)
            # instead of O(prompt * vocab) on prefill rounds
            payload = {"code": np.asarray(out[:, -1], np.float32)}
        else:
            payload = self.program.encode_wire(out)
        tp.publish(ServeCodeMsg(round_, lane, self.stage), payload,
                   actor=self.actor)
        self.slots_done += 1


@dataclasses.dataclass
class _Lane:
    """Driver-side state of one occupied request lane."""
    req: int
    max_new: int
    temperature: float
    emitted: int = 0           # tokens sampled so far (== next token index)


class ServeDriver:
    """Continuous-batching decode driver over any ``Transport``.

    The driver owns admission/retirement, sampling and latency tracking;
    stage compute lives in ``StageServer``s.  With ``servers`` given (the
    in-process and socket paths) the driver executes every timetable slot
    itself, in compiled slot order; with ``servers=None`` (actor fleets)
    it only publishes round plans and awaits each lane's last-stage
    logits, while ``ServeActor`` processes execute the same slots.

    Greedy parity contract: at ``temperature=0`` the emitted tokens are
    bit-identical to ``launch.serve.swarm_generate`` (the sequential
    single-process oracle) at the same seed, for any stage count,
    transport, or admission order — lanes are independent batch rows and
    sampling keys fold (seed, req, index) only.
    """

    def __init__(self, spec, transport, *, n_lanes: int, max_len: int,
                 servers: Optional[list] = None, seed: int = 0,
                 wire_codec: str = "none", timeout: float = 120.0):
        from repro.core.pipeline import ROLE_F, compile_timetable
        self.spec = spec
        self.transport = transport
        self.schema = transport.schema
        self.n_lanes = n_lanes
        self.max_len = max_len
        self.servers = servers
        self.seed = seed
        self.wire_codec = wire_codec
        self.timeout = timeout
        self.timetable = compile_timetable("decode", spec.n_stages, n_lanes)
        self._role_f = ROLE_F
        self.records: dict[int, RequestRecord] = {}
        self.rounds_run = 0

    # -- plumbing --------------------------------------------------------

    def publish_session_plan(self) -> None:
        """The one-shot session spec serve actors derive everything from."""
        self.transport.publish(ServePlanMsg(), {
            "n_stages": self.spec.n_stages,
            "n_lanes": self.n_lanes,
            "max_len": self.max_len,
            "wire_codec": self.wire_codec,
            "seed": self.seed,
        }, actor="serve-driver")

    def _sample(self, req: int, index: int, temperature: float, logits):
        from repro.runtime import stage_model as sm
        key = sm.request_key(self.seed, req, index)
        return int(np.asarray(sm.sample_token(
            jnp.asarray(logits), temperature=temperature, key=key))[0])

    # -- the round loop --------------------------------------------------

    def run(self, requests: Iterable[ServeRequest]) -> dict:
        """Serve every request to completion; returns {req: RequestRecord}.

        Admission and retirement happen strictly between rounds: a request
        joining mid-flight lands in a free lane as a *prefill* slot of the
        next round while already-running lanes decode — the lane plan is
        the active-lane mask, and untouched lanes' caches are untouched
        state, so running requests' tokens cannot change (the regression
        test pins this).
        """
        tp, schema = self.transport, self.schema
        queue = sorted(requests, key=lambda r: (r.arrival_round, r.req))
        lanes: list[Optional[_Lane]] = [None] * self.n_lanes
        self.publish_session_plan()
        rnd = self.rounds_run
        while queue or any(lanes):
            entries = []
            # admission: free lanes pick up arrived requests (FIFO)
            for li in range(self.n_lanes):
                if lanes[li] is None and queue \
                        and queue[0].arrival_round <= rnd:
                    r = queue.pop(0)
                    prompt = np.asarray(r.prompt, np.int32).reshape(1, -1)
                    assert prompt.shape[1] + r.max_new <= self.max_len, (
                        "prompt + max_new exceeds the lane KV capacity")
                    tp.publish(ServeRequestMsg(r.req), {
                        "tokens": prompt,
                        "max_new": int(r.max_new),
                        "temperature": float(r.temperature),
                    }, actor="serve-driver")
                    rec = self.records.setdefault(r.req, RequestRecord(r.req))
                    rec.submit_s = time.perf_counter()
                    lanes[li] = _Lane(r.req, int(r.max_new),
                                      float(r.temperature))
                    entries.append({"lane": li, "req": r.req,
                                    "phase": "prefill"})
                elif lanes[li] is not None:
                    ln = lanes[li]
                    entries.append({"lane": li, "req": ln.req,
                                    "phase": "decode",
                                    "in_index": ln.emitted - 1})
            if not entries:
                # nothing admissible yet (future arrival_round): publish
                # the empty round anyway so actor fleets stay in lockstep
                # with the driver's round counter (not GC'd — a late actor
                # may still need to read it; it is tiny and session-scoped)
                tp.publish(ServeRoundPlanMsg(rnd),
                           {"entries": [], "stop": False},
                           actor="serve-driver")
                rnd += 1
                continue
            tp.publish(ServeRoundPlanMsg(rnd),
                       {"entries": entries, "stop": False},
                       actor="serve-driver")
            if self.servers is not None:
                self._run_slots(rnd, entries)
            self._collect(rnd, entries, lanes)
            tp.delete_prefix(schema.serve_round_prefix(rnd))
            rnd += 1
        self.rounds_run = rnd
        return self.records

    def _run_slots(self, rnd: int, entries: list) -> None:
        """Execute one round's cells in compiled timetable order: slot t,
        stage s acts on lane ``micro[s, t]`` iff the lane plan marks that
        lane active.  This is the store-and-forward realization of the
        decode schedule — the same (s, lane) dependency order the on-mesh
        ``lax.switch`` executor walks."""
        tt = self.timetable
        by_lane = {e["lane"]: e for e in entries}
        for t in range(tt.n_slots):
            for s in range(tt.n_stages):
                if int(tt.role[s, t]) != self._role_f:
                    continue
                entry = by_lane.get(int(tt.micro[s, t]))
                if entry is None:
                    continue          # inactive lane: masked-off cell
                self.servers[s].process_slot(
                    self.transport, self.schema, rnd, entry)

    def _collect(self, rnd: int, entries: list, lanes: list) -> None:
        """Fetch each active lane's last-stage logits, sample, publish the
        token, retire finished requests."""
        tp, schema = self.transport, self.schema
        last = self.spec.n_stages - 1
        for entry in entries:
            li = int(entry["lane"])
            ln = lanes[li]
            payload = _serve_await(
                tp, schema.serve_code(rnd, li, last),
                actor="serve-driver", timeout=self.timeout)
            tok = self._sample(ln.req, ln.emitted, ln.temperature,
                               payload["code"])
            rec = self.records[ln.req]
            now = time.perf_counter()
            tp.publish(ServeTokenMsg(ln.req, ln.emitted),
                       np.asarray([[tok]], np.int32), actor="serve-driver")
            rec.tokens.append(tok)
            rec.token_s.append(now)
            if rec.first_token_s is None:
                rec.first_token_s = now
            ln.emitted += 1
            if ln.emitted >= ln.max_new:
                rec.done_s = now
                tp.publish(ServeDoneMsg(ln.req), {
                    "n_tokens": ln.emitted,
                    "ttft_s": rec.ttft,
                    "total_s": rec.total,
                }, actor="serve-driver")
                lanes[li] = None

    def stop_fleet(self) -> None:
        """Tell ServeActor processes the session is over (a stop plan in
        the next round slot)."""
        self.transport.publish(
            ServeRoundPlanMsg(self.rounds_run),
            {"entries": [], "stop": True}, actor="serve-driver")
