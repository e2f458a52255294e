"""Concurrent actor runtime: miners/validators as real OS processes.

The paper's SWARM peers (§2) are autonomous workers polling a globally
accessible store — no global barrier stepping.  Everything before this
module simulated that: PR 5 made the *store* a process, but every actor
still took turns inside one Python loop.  Here each miner and validator
is a ``spawn``-context process with its own ``SocketTransport`` (its
thread-safe store handle), pulling work off the store through a
``WorkQueue`` and publishing results the ``EventDriver``
(``repro.api.phases``) advances on.

Process model:

  * ``ActorProcess``   base: spawn entry, per-actor store connection, a
                       tiny TCP *health endpoint* (serde frames; ``ping``
                       answers a ``HeartbeatMsg`` envelope, ``stop``
                       requests a clean exit), the epoch loop (await
                       plan -> process -> next), clean shutdown;
  * ``MinerActor``     wraps a ``runtime.Miner``: derives its tick jobs
                       from the plan, awaits each input activation,
                       forwards/backwards, publishes activations,
                       gradients, the tick-loss watermark, its weight
                       upload and (sharded) its reduce work;
  * ``ValidatorActor`` replays its tracked miner from the store alone —
                       snapshot + activations + gradients + labels —
                       mirroring ``Validator.validate_epoch`` bit-exactly,
                       and publishes the ``ScoreMsg`` watermark;
  * ``ActorSupervisor``spawns/pings/stops the fleet and turns a dead
                       child into ``ActorDied`` instead of a hang;
  * ``ActorSwarm``     the ``Swarm`` facade over all of it —
                       ``Swarm.create(..., runtime="actors")`` builds one.

Determinism: the driver does every swarm RNG draw at plan time in the
lockstep order; actors interact only through bit-exact store payloads
and each actor processes its own jobs in tick order, so per-miner update
sequences — and the loss trajectory — equal the in-process oracle at the
same seed.  Payload-corrupting faults (tamper, free-ride) run *inside*
the owning actor (each child seeds its own fault RNG from the spec), so
adversarial scenarios work under the concurrent runtime too;
drop/straggle stay schedule-only (plan-time rolls in the parent).

Chaos additions (docs/CHAOS.md):

  * crash-resume — ``ActorSpec.snapshot_dir`` gives a miner a
    ``DiskSnapshotCache``; it snapshots at every epoch boundary and a
    respawned process restores the newest good snapshot, catches up to
    the newest visible anchor of its stage and fast-forwards to the
    in-flight epoch;
  * plan revisions — when the ``EventDriver`` re-plans around a death it
    publishes ``control/ep{E}/plan/r{R}``; blocked actors notice via the
    ``WorkQueue.abort_if`` hook (``WorkRescheduled``) and re-derive
    their work from the latest revision;
  * fault injection — ``ActorSpec.chaos`` wraps the child's transport in
    a seeded ``ChaosTransport``; ``ActorSpec.store_failover`` hands the
    child the warm-standby store addresses.
"""
from __future__ import annotations

import dataclasses
import socket
import threading
import time
from typing import Any, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import serde
from repro.api.config import SwarmConfig
from repro.api.keys import KeySchema
from repro.api.messages import (
    ActivationMsg,
    AnchorMsg,
    GradientMsg,
    HeartbeatMsg,
    ScoreMsg,
    SnapshotMsg,
    TickLossMsg,
    WeightUploadMsg,
)
from repro.api.phases import EventDriver, StageServer
from repro.api.swarm import Swarm
from repro.api.transport import SocketTransport
from repro.common import cosine_similarity
from repro.configs.base import ModelConfig, TrainConfig
from repro.core import butterfly, compression
from repro.runtime import stage_model as sm
from repro.runtime.chaos import wrap_transport
from repro.runtime.miner import Miner, inner_optimizer
from repro.runtime.network import FaultModel, MinerBehavior
from repro.runtime.snapshot_cache import DiskSnapshotCache
from repro.runtime.validator import COSINE_THRESHOLD


class ActorStopped(Exception):
    """Raised inside an actor when a stop request interrupts polling."""


class WorkRescheduled(Exception):
    """The work an actor was blocked on has been invalidated by a newer
    plan revision (``control/ep{E}/plan/r{R}``) — re-derive the work
    list from the latest revision instead of waiting for a key that may
    never arrive."""


class ActorDied(RuntimeError):
    """A spawned actor process exited while the swarm still needed it."""

    def __init__(self, actor: str, exitcode: Optional[int],
                 last: Optional[HeartbeatMsg] = None):
        msg = (f"actor process {actor!r} died (exit code {exitcode}) "
               f"while the epoch was in flight")
        if last is not None:
            msg += (f"; last heartbeat: epoch={last.epoch} "
                    f"items_done={last.items_done} state={last.state!r}")
        super().__init__(msg)
        self.actor = actor
        self.exitcode = exitcode
        self.last = last


class WorkQueue:
    """Pull-based work discovery: an actor blocks on the store key that
    carries its next input instead of being called by a driver.

    ``await_key`` blocks until the key appears, a stop request lands
    (``ActorStopped``), the ``liveness`` hook raises (driver-side: a
    crashed peer), the ``abort_if`` hook reports the wait is moot
    (``WorkRescheduled`` — a plan revision reassigned the work), or
    ``timeout`` expires.  When the transport offers ``wait_for``
    (``SocketTransport`` against a ``StoreServer``) the wait parks
    server-side on a condition variable in bounded slices — zero CPU
    while idle; otherwise it falls back to exists-polling at
    ``poll_interval``."""

    def __init__(self, transport, poll_interval: float = 0.001,
                 timeout: float = 120.0, liveness=None,
                 stop_event: Optional[threading.Event] = None,
                 liveness_every: int = 25):
        self.transport = transport
        self.poll_interval = poll_interval
        self.timeout = timeout
        self.liveness = liveness
        self.stop_event = stop_event
        self.liveness_every = max(int(liveness_every), 1)
        # chaos hook: a zero-arg callable; when it returns True the
        # current wait is abandoned with WorkRescheduled (installed by
        # actors while a plan revision may still land)
        self.abort_if = None

    wait_slice = 0.25    # bounded server-side park: stop/liveness cadence

    def await_key(self, key: str) -> None:
        deadline = time.monotonic() + self.timeout
        wait_for = getattr(self.transport, "wait_for", None)
        polls = 0
        while True:
            if self.stop_event is not None and self.stop_event.is_set():
                raise ActorStopped(key)
            if self.liveness is not None \
                    and polls % self.liveness_every == 0:
                self.liveness()
            if self.abort_if is not None and self.abort_if():
                raise WorkRescheduled(key)
            if wait_for is not None:
                if wait_for(key, timeout=self.wait_slice):
                    return
            else:
                if self.transport.exists(key):
                    return
                time.sleep(self.poll_interval)
            if time.monotonic() >= deadline:
                raise TimeoutError(
                    f"work queue timed out after {self.timeout}s "
                    f"awaiting {key!r}")
            polls += 1

    def get(self, key: str, actor: str = "?") -> Any:
        self.await_key(key)
        return self.transport.get(key, actor=actor)


@runtime_checkable
class Actor(Protocol):
    """The surface every actor-process implementation must provide (the
    swarmlint ``protocol-conformance`` rule binds ``*Actor`` classes to
    this protocol; ``ActorProcess`` supplies the base implementation)."""
    actor: str

    def setup(self) -> None: ...

    def process_epoch(self, plan: dict) -> None: ...

    def status(self) -> HeartbeatMsg: ...

    def shutdown(self) -> None: ...


@dataclasses.dataclass(frozen=True)
class ActorSpec:
    """Picklable spawn arguments: everything a child process needs to
    rebuild its world deterministically (params re-derive from the seed,
    they never cross the process boundary at spawn).

    Chaos fields: ``behavior`` makes the child run its own payload
    faults (tamper/free-ride) with a per-uid seeded RNG;
    ``snapshot_dir`` turns on the crash-resume ``DiskSnapshotCache``;
    ``chaos`` (a ``runtime.chaos.FaultSchedule``) wraps the child's
    transport; ``store_failover`` lists warm-standby store addresses.

    ``platform`` pins the child's JAX backend (``"cpu"``) before it
    touches a device.  A TPU chip belongs to one process: a fleet spawned
    by a parent that holds the chip must pin every child to the CPU, or
    ``ActorSupervisor.spawn`` refuses it (``chip_owner_error``)."""
    kind: str                 # "miner" | "validator" | "server"
    uid: int
    stage: int                # -1 for validators
    model_cfg: ModelConfig
    config: SwarmConfig
    train_cfg: TrainConfig
    store_address: tuple
    start_epoch: int = 0
    behavior: Optional[MinerBehavior] = None
    snapshot_dir: Optional[str] = None
    chaos: Any = None         # FaultSchedule | None
    store_failover: tuple = ()
    platform: Optional[str] = None


class ActorProcess:
    """Base actor: spawn-context process body, own store connection,
    heartbeat/health endpoint over a tiny TCP socket, clean shutdown.

    The epoch loop awaits ``control/ep{E}/plan``, hands the decoded plan
    to ``process_epoch`` and advances; a plan with ``stop=True`` (or a
    ``stop`` op on the health endpoint) ends the loop cleanly."""

    health_poll = 0.2         # accept() timeout: stop-flag check cadence
    schema_version = 4        # key plane the actor speaks (serve uses v5)

    def __init__(self, spec: ActorSpec):
        self.spec = spec
        self.actor = f"{spec.kind}{spec.uid}"
        self.epoch = spec.start_epoch
        self.items_done = 0
        self.state = "init"
        self.transport: Optional[SocketTransport] = None
        self.queue: Optional[WorkQueue] = None
        self._stop = threading.Event()
        self._health_sock: Optional[socket.socket] = None
        self.model_spec: Optional[sm.SwarmModelSpec] = None

    # -- lifecycle -------------------------------------------------------

    def setup(self) -> None:
        S = self.spec.config
        self.transport = SocketTransport(
            self.spec.store_address,
            schema=KeySchema(version=self.schema_version),
            failover=tuple(self.spec.store_failover or ()))
        if self.spec.chaos is not None:
            self.transport = wrap_transport(self.transport,
                                            self.spec.chaos,
                                            actor_tag=self.actor)
        self.queue = WorkQueue(self.transport, stop_event=self._stop)
        self.model_spec = sm.SwarmModelSpec(
            self.spec.model_cfg, S.n_stages, S.compress, S.bottleneck_dim)

    # -- plan revisions (graceful degradation) ---------------------------

    def _latest_plan(self, epoch: int, plan: dict) -> dict:
        """Fold in every published plan revision for ``epoch`` and arm
        the work queue's abort hook on the next (still unpublished) one,
        so a blocked await abandons work a revision reassigns."""
        schema = self.transport.schema
        if schema.version < 4:
            return plan
        rev = int(plan.get("rev", 0))
        while True:
            key = schema.plan_rev(epoch, rev + 1)
            if not self.transport.exists(key):
                break
            plan = self.transport.get(key, actor=self.actor)
            rev = int(plan.get("rev", rev + 1))
        nxt = schema.plan_rev(epoch, rev + 1)
        self.queue.abort_if = lambda: self.transport.exists(nxt)
        return plan

    def _newest_plan_epoch(self) -> Optional[int]:
        """Highest epoch with a visible plan — the fast-forward target
        for an actor that fell behind the swarm (crash-resume)."""
        schema = self.transport.schema
        best = None
        for key in self.transport.keys(""):
            try:
                parsed = schema.parse(key)
            except ValueError:
                continue
            if parsed.kind == "plan":
                ep = parsed.fields["epoch"]
                if best is None or ep > best:
                    best = ep
        return best

    def status(self) -> HeartbeatMsg:
        import os
        return HeartbeatMsg(self.actor, pid=os.getpid(), epoch=self.epoch,
                            items_done=self.items_done, state=self.state)

    def shutdown(self) -> None:
        self._stop.set()
        if self._health_sock is not None:
            try:
                self._health_sock.close()
            except OSError:
                pass
            self._health_sock = None
        if self.transport is not None:
            self.transport.close()

    def process_epoch(self, plan: dict) -> None:
        raise NotImplementedError

    # -- health endpoint -------------------------------------------------

    def _serve_health(self) -> None:
        srv = self._health_sock
        while not self._stop.is_set():
            try:
                conn, _ = srv.accept()
            except (OSError, socket.timeout):
                if self._stop.is_set():
                    return
                continue
            try:
                conn.settimeout(2.0)
                while True:
                    frame = serde.recv_frame(conn)
                    if frame is None:
                        break
                    req = serde.loads(frame)
                    if req.get("op") == "stop":
                        self.state = "stopping"
                        self._stop.set()
                    serde.send_frame(conn,
                                     serde.encode_message(self.status()))
            except (OSError, socket.timeout, ConnectionError):
                pass
            finally:
                try:
                    conn.close()
                except OSError:
                    pass

    def run(self, ready_queue: Any = None) -> None:
        """Blocking process body: health endpoint up, report ready, loop
        epochs until a stop plan / stop ping / ActorStopped."""
        self.setup()
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.bind(("127.0.0.1", 0))
        srv.listen(4)
        srv.settimeout(self.health_poll)
        self._health_sock = srv
        threading.Thread(target=self._serve_health,
                         name=f"{self.actor}-health", daemon=True).start()
        if ready_queue is not None:
            ready_queue.put((self.actor, srv.getsockname()[:2]))
        try:
            self._main_loop()
        except ActorStopped:
            pass
        finally:
            self.state = "stopped"
            self.shutdown()

    def _main_loop(self) -> None:
        """Plan-driven work loop; ``ServeActor`` overrides this with the
        round-plan variant (same health/ready/stop machinery in run())."""
        while not self._stop.is_set():
            self.state = "awaiting-plan"
            plan_key = self.transport.schema.plan(self.epoch)
            while True:
                try:
                    self.queue.await_key(plan_key)
                    break
                except TimeoutError:
                    # idle between epochs is not a failure — but a
                    # resumed actor may be awaiting a plan the swarm
                    # GC'd: fast-forward to the newest visible one
                    newest = self._newest_plan_epoch()
                    if newest is not None and newest > self.epoch:
                        self.epoch = newest
                        plan_key = self.transport.schema.plan(
                            self.epoch)
                    continue
            plan = self.transport.get(plan_key, actor=self.actor)
            if plan.get("stop"):
                break
            self.state = "working"
            self.process_epoch(plan)
            self.epoch += 1


class MinerActor(ActorProcess):
    """A ``runtime.Miner`` driven by the store instead of the driver.

    Crash-resume: with ``spec.snapshot_dir`` set the miner snapshots its
    full state (params, opt state, inner step) to a
    ``DiskSnapshotCache`` at every epoch boundary, *before* any tick
    mutates it.  A respawned process restores the newest good snapshot,
    downloads the newest anchor of its stage (the store's catch-up
    artifact), fast-forwards to the in-flight epoch and rejoins — it
    never restarts from the seed."""

    def __init__(self, spec: ActorSpec):
        super().__init__(spec)
        self.miner: Optional[Miner] = None
        self._cache: Optional[DiskSnapshotCache] = None
        self.resumed_from: Optional[int] = None
        b = spec.behavior
        self._behavior = b if b is not None and not b.honest else None
        # the child's own fault RNG (the lockstep timeline draws from the
        # parent's FaultModel; here corruption is owned by the actor)
        self._faults = FaultModel(
            {spec.uid: b} if b is not None else {},
            seed=(spec.config.seed * 7919 + spec.uid) & 0x7FFFFFFF)

    def setup(self) -> None:
        super().setup()
        S = self.spec.config
        stage = self.spec.stage
        # same init as Swarm.register_miner: params copy the stage anchor,
        # which is init_stage_params at the folded seed — re-derived here
        # so no weights cross the spawn boundary
        params = sm.init_stage_params(
            jax.random.fold_in(jax.random.key(S.seed), stage),
            self.model_spec, stage)
        self.miner = Miner(self.spec.uid, stage, self.model_spec,
                           jax.tree.map(jnp.copy, params), self.transport,
                           self.spec.train_cfg)
        if self.spec.snapshot_dir:
            self._cache = DiskSnapshotCache(self.spec.snapshot_dir)
            self._try_resume()

    # -- crash-resume ----------------------------------------------------

    def _try_resume(self) -> None:
        """Restore the newest good snapshot and replay forward: load the
        newest visible anchor of this stage, then fast-forward the epoch
        cursor to the newest visible plan (corrupt snapshots are
        quarantined by the cache and the next older one used)."""
        m = self.miner
        got = self._cache.restore_latest(m.snapshot())
        if got is None:
            return                      # fresh actor: seed-derived state
        snap_epoch, tree, _meta = got
        m.params = jax.tree.map(jnp.asarray, tree["params"])
        m.opt_state = jax.tree.map(jnp.asarray, tree["opt_state"])
        m.inner_step = jnp.asarray(tree["inner_step"], jnp.int32)
        self.epoch = max(self.epoch, snap_epoch)
        self.resumed_from = snap_epoch
        schema = self.transport.schema
        best = None
        for key in self.transport.keys(""):
            try:
                parsed = schema.parse(key)
            except ValueError:
                continue
            if parsed.kind == "anchor" \
                    and parsed.fields["stage"] == m.stage:
                ep = parsed.fields["epoch"]
                if ep >= snap_epoch and (best is None or ep > best):
                    best = ep
        if best is not None:
            m.load_weights_vector(np.asarray(self.transport.get(
                schema.anchor(best, m.stage), actor=self.actor)))
            # an anchor for epoch E means E *completed* — replaying E is
            # impossible anyway (its activation/gradient planes are GC'd
            # at epoch end), so rejoin at the boundary after it
            self.epoch = max(self.epoch, best + 1)
        newest = self._newest_plan_epoch()
        if newest is not None and newest > self.epoch:
            self.epoch = newest
        self.state = f"resumed@{snap_epoch}"
        # store-side marker so scenarios can assert a real resume
        self.transport.put(schema.heartbeat(self.actor),
                           {"resumed_from": snap_epoch,
                            "epoch": self.epoch},
                           actor=self.actor)

    # -- the epoch -------------------------------------------------------

    def process_epoch(self, plan: dict) -> None:
        m = self.miner
        epoch = plan["epoch"]
        m.reset_epoch()
        if self._cache is not None:
            # epoch-boundary snapshot, before any tick mutates state: a
            # respawn restores exactly here
            self._cache.save(epoch, m.snapshot(),
                             {"uid": m.uid, "stage": m.stage})
        plan = self._latest_plan(epoch, plan)
        if m.uid not in set(plan.get("dead", ())) \
                and m.uid in set(plan["tracked"].values()):
            # epoch-start snapshot, before any tick mutates state: the
            # tracked validator replays from exactly here
            self.transport.publish(SnapshotMsg(epoch, m.uid), m.snapshot(),
                                   actor=self.actor)
        done: set = set()
        self._uploaded = False
        self._reduced = False
        self._shard_ex = None
        while True:
            dropped = set(plan.get("dropped", ()))
            orphaned = set(plan.get("orphaned", ()))
            try:
                for tick, uids in plan["ticks"]:
                    uids = tuple(uids)
                    if uids[m.stage] != m.uid or tick in done \
                            or tick in dropped:
                        continue
                    brk = self._orphan_break(plan, uids) \
                        if tick in orphaned else None
                    self._process_tick(epoch, tick, uids,
                                       orphan_break=brk)
                    done.add(tick)
                    self.items_done += 1
                # my ticks (under this fold of the plan) are done — but a
                # revision can still hand me a dead peer's remaining work
                # while I park at the full-sync anchor, so the anchor
                # await keeps the revision abort armed and a reschedule
                # re-enters the tick scan above.  Only the rev check
                # keeps this loop finite.
                rev = plan.get("rev", 0)
                plan = self._latest_plan(epoch, plan)
                if plan.get("rev", 0) != rev:
                    continue           # fresh revision: rescan for work
                if plan["merge"]:
                    self._share_and_sync(epoch, plan)
                break
            except WorkRescheduled:
                plan = self._latest_plan(epoch, plan)
        self.queue.abort_if = None

    @staticmethod
    def _orphan_break(plan: dict, uids: tuple) -> Optional[int]:
        """Lowest dead stage on this pathway: backward is broken *below*
        it (the dead miner never forwarded its gradient), intact above."""
        dead = set(plan.get("dead", ()))
        stages = [plan["stage_of"][u] for u in uids if u in dead]
        return min(stages) if stages else None

    def _process_tick(self, epoch: int, tick: int, uids: tuple,
                      orphan_break: Optional[int] = None) -> None:
        m, schema = self.miner, self.transport.schema
        s, last = m.stage, self.spec.config.n_stages - 1
        in_key = schema.tokens(epoch, tick) if s == 0 \
            else schema.activation(epoch, tick, s - 1, uids[s - 1])
        out_key = schema.activation(epoch, tick, s, m.uid)
        if orphan_break is not None:
            # an orphaned tick's forward chain completed before the
            # death (its loss is published) — never re-forward, params
            # may have moved since; only the backward may be pending
            if s == last or s < orphan_break:
                return               # chain broken below the casualty
            g = m.backward(in_key, self._decode_gradient(
                self.queue.get(schema.gradient_for(out_key), self.actor)))
            if s > 0:
                self._publish_gradient(epoch, tick, s - 1, uids[s - 1], g)
            return
        self.queue.await_key(in_key)
        out = m.forward(tick, in_key, out_key)
        b = self._behavior
        if b is not None and s < last \
                and (b.free_ride or b.tamper_activations > 0):
            # adversarial republish over the honest output — validators
            # catch the mismatch on replay, CLASP the loss inflation
            # (mirrors the lockstep TrainingPhase, but actor-owned)
            corrupted = self._faults.corrupt_activation(
                m.uid, np.asarray(out, np.float32))
            self.transport.publish(
                ActivationMsg(epoch, tick, s, m.uid),
                jnp.asarray(corrupted).astype(jnp.asarray(out).dtype),
                actor=self.actor)
        if s == last:
            lab_key = schema.labels(epoch, tick)
            loss, g = m.backward_last(in_key,
                                      self.queue.get(lab_key, self.actor))
            # the training watermark the EventDriver folds into records
            self.transport.publish(TickLossMsg(epoch, tick), float(loss),
                                   actor=self.actor)
        else:
            g_key = schema.gradient_for(out_key)
            g = m.backward(in_key, self._decode_gradient(
                self.queue.get(g_key, self.actor)))
        if s > 0:
            self._publish_gradient(epoch, tick, s - 1, uids[s - 1], g)

    def _publish_gradient(self, epoch: int, tick: int, stage: int,
                          uid: int, g) -> None:
        msg = GradientMsg(epoch, tick, stage, uid)
        if self.spec.config.wire_codec == "int8":
            # the lockstep driver's int8 gradient wire, producer-side; the
            # extra "dtype" key lets the consumer replicate the exact
            # decode->astype the in-process loop applies (it knows g's
            # dtype in-process; over the wire it must be carried)
            flat = jnp.ravel(jnp.asarray(g, jnp.float32))
            payload = dict(compression.encode(flat, "int8"),
                           shape=tuple(np.shape(g)),
                           dtype=str(jnp.asarray(g).dtype))
            self.transport.publish(msg, payload, actor=self.actor)
        else:
            self.transport.publish(msg, g, actor=self.actor)

    def _decode_gradient(self, g):
        if isinstance(g, dict) and g.get("codec"):
            return jnp.reshape(compression.decode(g), g["shape"]).astype(
                serde._np_dtype(g["dtype"]))
        return g

    # -- sharing + sync --------------------------------------------------

    def _share_and_sync(self, epoch: int, plan: dict) -> None:
        m, S = self.miner, self.spec.config
        schema = self.transport.schema
        qual = plan["qualified"].get(m.stage, ())
        if m.uid in qual:
            if not self._uploaded:
                # once per epoch: a reschedule from the anchor park below
                # can re-enter here after re-planned ticks moved the
                # weights, and republishing the upload key with different
                # bits would be a digest conflict — the merge averages
                # the pre-revision vector, which is what the plan-time
                # layout expects
                self._uploaded = True
                vec = m.weights_vector()
                b = self._behavior
                if b is not None and b.tamper_weights > 0:
                    # dishonest upload (the agreement matrix exposes it)
                    vec = self._faults.corrupt_weights(
                        m.uid, np.asarray(vec, np.float32))
                if S.sync_mode == "sharded":
                    self._shard_upload(epoch, tuple(qual), vec)
                else:
                    payload = compression.encode(jnp.asarray(vec),
                                                 S.share_codec)
                    self.transport.publish(
                        WeightUploadMsg(epoch, m.stage, m.uid,
                                        codec=S.share_codec),
                        payload, actor=self.actor)
            if S.sync_mode == "sharded" and not self._reduced:
                self._reduce_shards(plan, tuple(qual))
        if m.stage in plan["qualified"]:
            # full sync: everyone in a merged stage (stragglers included)
            # downloads the anchor the driver publishes.  The await keeps
            # the revision abort armed: WorkRescheduled propagates to the
            # process_epoch loop, which folds the revision and rescans
            # for re-planned ticks before parking here again.
            anchor = AnchorMsg(epoch, m.stage)
            self.queue.await_key(anchor.key(schema))
            m.load_weights_vector(self.transport.fetch(anchor,
                                                       actor=self.actor))

    def _shard_upload(self, epoch: int, qual: tuple, vec) -> None:
        m, S = self.miner, self.spec.config
        align = compression.INT8_BLOCK if S.share_codec == "int8" else 1
        plan_b = butterfly.make_plan(len(qual), int(vec.shape[0]),
                                     seed=S.seed + epoch * 131 + m.stage,
                                     align=align)
        self._shard_ex = butterfly.ButterflyExecutor(
            plan_b, self.transport, epoch=epoch, stage=m.stage,
            uids=list(qual), codec=S.share_codec)
        self._shard_ex.upload_vector(qual.index(m.uid), vec,
                                     actor=self.actor)

    def _reduce_shards(self, plan: dict, qual: tuple) -> None:
        """Input barrier + reduce.  ``reduce_one`` masks *missing*
        uploads out of the merge, so every input must exist before
        reducing — await them all, except a dead peer's, which will
        never come (the store is immutable, so every live reducer masks
        the same set and the redundant copies stay bit-identical).  The
        barrier keeps the revision abort armed — a mid-barrier death
        reschedules and re-enters with the new ``dead`` list; only the
        reduce itself publishes, and runs uninterruptible."""
        m = self.miner
        ex, idx = self._shard_ex, qual.index(m.uid)
        dead = set(plan.get("dead", ()))
        for a in ex.assignments_for(idx):
            for i, key in enumerate(a.upload_keys):
                if qual[i] in dead:
                    continue
                self.queue.await_key(key)
        armed, self.queue.abort_if = self.queue.abort_if, None
        try:
            b = self._behavior
            m.run_reduce(ex, idx,
                         tamper=b.tamper_weights if b is not None else 0.0)
        finally:
            self.queue.abort_if = armed
        self._reduced = True


class ValidatorActor(ActorProcess):
    """Replays its tracked miner purely from store artifacts (snapshot,
    activations, gradients, labels), mirroring
    ``Validator.validate_epoch`` operation for operation, then publishes
    the ``ScoreMsg`` watermark the driver's ledger waits on."""

    def __init__(self, spec: ActorSpec):
        super().__init__(spec)
        self.update = None

    def setup(self) -> None:
        super().setup()
        # the update program Miner runs: replayed updates must track the
        # miner's own bits
        _, self.update = inner_optimizer(self.spec.train_cfg)

    def process_epoch(self, plan: dict) -> None:
        S = self.spec.config
        schema = self.transport.schema
        epoch = plan["epoch"]
        plan = self._latest_plan(epoch, plan)
        uid = plan["tracked"].get(self.spec.uid)
        if uid is None:
            self.queue.abort_if = None
            return
        stage = plan["stage_of"][uid]
        role = self.model_spec.role(stage)
        params = opt_state = inner_step = None

        checked = passed = 0
        validated = 0.0
        min_cos = 1.0
        done: set = set()
        while True:
            if uid in set(plan.get("dead", ())):
                # tracked miner is the casualty: publish the partial
                # score over what was already checked (the driver's
                # ledger is waiting on this watermark)
                break
            dropped = set(plan.get("dropped", ()))
            orphaned = set(plan.get("orphaned", ()))
            items = [(t, tuple(uids)) for t, uids in plan["ticks"]
                     if tuple(uids)[stage] == uid and t not in dropped]
            if S.validate_max_items is not None:
                items = items[:S.validate_max_items]
            try:
                if params is None:
                    snap = self.queue.get(schema.snapshot(epoch, uid),
                                          self.actor)
                    params = jax.tree.map(jnp.asarray, snap["params"])
                    opt_state = jax.tree.map(jnp.asarray,
                                             snap["opt_state"])
                    inner_step = jnp.asarray(snap["inner_step"])
                for tick, uids in items:
                    if tick in done:
                        continue
                    brk = MinerActor._orphan_break(plan, uids) \
                        if tick in orphaned else None
                    sample_key = schema.tokens(epoch, tick) if stage == 0 \
                        else schema.activation(epoch, tick, stage - 1,
                                               uids[stage - 1])
                    out_key = schema.activation(epoch, tick, stage, uid)
                    x_in = self.queue.get(sample_key, self.actor)
                    mine = sm.stage_forward(params, x_in, self.model_spec,
                                            role)
                    theirs = self.queue.get(out_key, self.actor)
                    cos = float(cosine_similarity(
                        jnp.asarray(mine, jnp.float32),
                        jnp.asarray(theirs, jnp.float32)))
                    checked += 1
                    min_cos = min(min_cos, cos)
                    ok = cos >= COSINE_THRESHOLD
                    passed += int(ok)
                    if brk is not None and stage < brk:
                        # orphaned below the break: the miner never ran
                        # this backward either — forward check only
                        if ok:
                            validated += 1.0
                        done.add(tick)
                        self.items_done += 1
                        continue
                    # every completed pathway item ran a backward; replay
                    # it so later items line up (Validator.validate_epoch)
                    if role == "last":
                        labels = self.queue.get(schema.labels(epoch, tick),
                                                self.actor)
                        _, g_params, _ = sm.last_stage_loss_and_grads(
                            params, x_in, labels, self.model_spec)
                    else:
                        g_out = self.queue.get(schema.gradient_for(out_key),
                                               self.actor)
                        if isinstance(g_out, dict) and g_out.get("codec"):
                            g_out = jnp.reshape(compression.decode(g_out),
                                                g_out["shape"])
                        g_params, _ = sm.stage_backward(
                            params, x_in, g_out, self.model_spec, role)
                    params, opt_state, inner_step = self.update(
                        g_params, opt_state, params, inner_step)
                    if ok:
                        validated += 1.0
                    done.add(tick)
                    self.items_done += 1
                break
            except WorkRescheduled:
                plan = self._latest_plan(epoch, plan)

        self.queue.abort_if = None
        self.transport.publish(
            ScoreMsg(epoch, self.spec.uid, uid),
            np.asarray([validated, checked, passed, min_cos], np.float32),
            actor=self.actor)


class ServeActor(ActorProcess):
    """One decode-pipeline stage as a store-driven process (kind
    ``"server"``): the serve-plane sibling of ``MinerActor``.

    The loop speaks KeySchema v5: await the session plan (``serve/plan``
    — lane count, max length, wire codec, weight seed), build the
    ``StageServer`` with deterministically re-derived stage params, then
    process round plans (``serve/round{N}/plan``) in order until one
    carries ``stop``.  All compute is deterministic and sampling lives in
    the driver, so an actor fleet serves tokens bit-identical to the
    in-process pipeline and the sequential oracle."""

    schema_version = 5

    def __init__(self, spec: ActorSpec):
        super().__init__(spec)
        self.server: Optional[StageServer] = None
        self.round = 0

    def process_epoch(self, plan: dict) -> None:
        """One round plan: run this stage's timetable cells.  For a fixed
        stage the decode timetable orders slots by ascending lane
        (``f[(s, m)] = s + m``), which is the order entries arrive in."""
        schema = self.transport.schema
        for entry in plan["entries"]:
            self.server.process_slot(self.transport, schema,
                                     self.round, entry)
            self.items_done += 1

    def _main_loop(self) -> None:
        schema = self.transport.schema
        self.state = "awaiting-plan"
        while not self._stop.is_set():
            try:
                self.queue.await_key(schema.serve_plan())
                break
            except TimeoutError:
                continue          # no session yet — idle, not a failure
        if self._stop.is_set():
            return
        sess = self.transport.get(schema.serve_plan(), actor=self.actor)
        self.server = StageServer(
            self.model_spec, self.spec.stage,
            sm.serve_stage_params(self.model_spec, int(sess["seed"]),
                                  self.spec.stage),
            n_lanes=int(sess["n_lanes"]), max_len=int(sess["max_len"]),
            wire_codec=str(sess["wire_codec"]))
        while not self._stop.is_set():
            self.state = "awaiting-plan"
            plan_key = schema.serve_round_plan(self.round)
            try:
                self.queue.await_key(plan_key)
            except TimeoutError:
                continue          # idle between rounds is not a failure
            plan = self.transport.get(plan_key, actor=self.actor)
            if plan.get("stop"):
                break
            self.state = "working"
            self.process_epoch(plan)
            self.round += 1
            self.epoch = self.round   # heartbeat visibility


_ACTOR_KINDS = {"miner": MinerActor, "validator": ValidatorActor,
                "server": ServeActor}


def _child_main(spec: ActorSpec, ready_queue: Any) -> None:
    """Spawn entry point (module-level: the child pickles a reference)."""
    if spec.platform is not None:
        jax.config.update("jax_platforms", spec.platform)
    _ACTOR_KINDS[spec.kind](spec).run(ready_queue)


def chip_owner_error(specs: list) -> Optional[str]:
    """Why ``specs`` cannot be spawned from this process, or None.

    The parent of a fleet always runs JAX itself (anchors, sampling), so
    on a TPU host it holds the chip, and a child that reaches for the
    chip would fail on libtpu's lock or wait on it.  Children pinned to
    the CPU through ``ActorSpec.platform`` are fine."""
    if jax.default_backend() != "tpu":
        return None
    unpinned = [f"{s.kind}{s.uid}" for s in specs if s.platform != "cpu"]
    if not unpinned:
        return None
    return (f"cannot spawn actors {unpinned} from a process that holds the "
            f"TPU: a chip belongs to one process.  Run the in-process "
            f"runtime on the chip, or pin every child to the CPU with "
            f"ActorSpec(platform='cpu')")


class ActorSupervisor:
    """Owns the actor process fleet: spawn, health pings, stop, the
    liveness check that turns a dead child into ``ActorDied``, and the
    chaos controls — ``kill`` (hard crash), ``forget`` (drop a dead
    child from liveness so the epoch can degrade around it) and
    ``respawn`` (relaunch from the recorded spec, crash-resume)."""

    def __init__(self):
        self.procs: dict[str, Any] = {}
        self.health: dict[str, tuple] = {}
        self.specs: dict[str, ActorSpec] = {}
        self.last_seen: dict[str, HeartbeatMsg] = {}

    def spawn(self, specs: list) -> None:
        import multiprocessing as mp
        import queue as queue_mod

        err = chip_owner_error(specs)
        if err is not None:
            raise RuntimeError(err)
        ctx = mp.get_context("spawn")
        ready = ctx.Queue()
        for spec in specs:
            name = f"{spec.kind}{spec.uid}"
            proc = ctx.Process(target=_child_main, args=(spec, ready),
                               daemon=True, name=name)
            proc.start()
            self.procs[name] = proc
            self.specs[name] = spec
        pending = len(specs)
        while pending:
            try:
                name, addr = ready.get(timeout=0.5)
                self.health[name] = (str(addr[0]), int(addr[1]))
                pending -= 1
            except queue_mod.Empty:
                for name, proc in self.procs.items():
                    if not proc.is_alive():
                        raise ActorDied(name, proc.exitcode,
                                        last=self.last_seen.get(name))

    def _health_request(self, name: str, op: str,
                        timeout: float = 5.0) -> HeartbeatMsg:
        addr = self.health[name]
        with socket.create_connection(addr, timeout=timeout) as sock:
            serde.send_frame(sock, serde.dumps({"op": op}))
            frame = serde.recv_frame(sock)
        if frame is None:
            raise ConnectionError(f"health endpoint of {name!r} closed")
        return serde.decode_message(frame)

    def ping(self, name: str) -> HeartbeatMsg:
        hb = self._health_request(name, "ping")
        self.last_seen[name] = hb
        return hb

    def progress(self) -> dict[str, HeartbeatMsg]:
        """Last known ``HeartbeatMsg`` per actor — live pings where the
        health endpoint answers, the cached heartbeat where it doesn't
        (a stalled or dead child keeps its last report)."""
        out: dict[str, HeartbeatMsg] = {}
        for name in sorted(self.procs):
            try:
                out[name] = self.ping(name)
            except (OSError, ConnectionError):
                hb = self.last_seen.get(name)
                if hb is not None:
                    out[name] = hb
        return out

    def stop(self, name: str) -> None:
        try:
            self._health_request(name, "stop", timeout=2.0)
        except (OSError, ConnectionError):
            pass                     # already gone: stopping is idempotent

    def kill(self, name: str) -> None:
        """Hard-crash a child (SIGTERM, no cleanup) — the chaos
        scenarios' crash primitive.  The dead process stays registered,
        so the next ``check()`` surfaces ``ActorDied`` and the driver's
        graceful degradation takes over."""
        proc = self.procs[name]
        if proc.is_alive():
            proc.terminate()
        proc.join(timeout=5.0)

    def forget(self, name: str) -> None:
        """Drop a dead child from liveness tracking (the driver calls
        this after re-planning around it) so ``check()`` stops raising
        for a casualty the epoch already degraded around."""
        self.procs.pop(name, None)
        self.health.pop(name, None)

    def respawn(self, name: str,
                start_epoch: Optional[int] = None) -> None:
        """Relaunch a (dead) actor from its recorded spec.  With a
        ``snapshot_dir`` in the spec the child crash-resumes from its
        newest good snapshot; ``start_epoch`` seeds the epoch cursor."""
        spec = self.specs[name]
        if start_epoch is not None:
            spec = dataclasses.replace(spec, start_epoch=start_epoch)
        self.forget(name)
        self.spawn([spec])

    def check(self) -> None:
        """Raise ``ActorDied`` if any child exited — called from await
        loops so a crash surfaces immediately instead of as a timeout.
        The error carries the casualty's last heartbeat (epoch,
        items_done, state) for the post-mortem."""
        for name, proc in self.procs.items():
            if not proc.is_alive():
                raise ActorDied(name, proc.exitcode,
                                last=self.last_seen.get(name))

    def join_all(self, timeout: float = 10.0) -> None:
        deadline = time.monotonic() + timeout
        for proc in self.procs.values():
            proc.join(timeout=max(deadline - time.monotonic(), 0.1))

    def terminate_all(self) -> None:
        for proc in self.procs.values():
            if proc.is_alive():
                proc.terminate()
        for proc in self.procs.values():
            proc.join(timeout=2.0)

    @property
    def names(self) -> list[str]:
        return sorted(self.procs)


class ActorSwarm(Swarm):
    """``Swarm`` whose miners and validators are concurrent processes.

    The parent keeps the facade state (anchors, outer optimizer, ledger,
    corpus, RNG — and placeholder ``Miner`` objects used only for uids /
    stages / census), the ``EventDriver`` timeline, and the supervisor;
    all forward/backward/replay compute runs in the children.  With no
    ``store_address`` an in-process threaded ``StoreServer`` is started
    and owned (real sockets, no extra spawn cost); pass an address to
    point the whole swarm at an external store process instead.

        swarm = Swarm.create(model_cfg, cfg, runtime="actors")
        try:
            stats = swarm.run(3)      # actors spawn on first epoch
        finally:
            swarm.shutdown()
    """

    def __init__(self, model_cfg: ModelConfig,
                 config: Optional[SwarmConfig] = None, *,
                 faults: Optional[FaultModel] = None,
                 train_cfg: Optional[TrainConfig] = None,
                 store_address: Optional[tuple] = None,
                 driver: Optional[EventDriver] = None,
                 snapshot_root: Optional[str] = None,
                 chaos: Any = None,
                 store_standby: bool = False):
        config = config or SwarmConfig()
        faults = faults or FaultModel({}, seed=config.seed)
        self._own_server = None
        self._standby = None
        if store_address is None:
            from repro.runtime.store_server import StoreServer
            self._own_server = StoreServer().start()
            store_address = self._own_server.address
            if store_standby:
                # warm standby: the primary mirrors every mutation
                # synchronously; clients carry the standby address and
                # fail over when the primary drops
                self._standby = StoreServer().start()
                self._own_server.mirror_to(self._standby.address)
        elif store_standby:
            raise ValueError(
                "store_standby=True needs the swarm-owned store (omit "
                "store_address); an external store manages its own "
                "replica")
        self.store_address = (str(store_address[0]), int(store_address[1]))
        self._failover = ((self._standby.address,)
                          if self._standby is not None else ())
        transport = SocketTransport(self.store_address,
                                    schema=KeySchema(version=4),
                                    failover=self._failover)
        super().__init__(model_cfg, config, faults=faults,
                         transport=transport, train_cfg=train_cfg,
                         driver=driver or EventDriver())
        self.supervisor = ActorSupervisor()
        self._started = False
        self.dead_uids: set = set()
        self.snapshot_root = snapshot_root
        self.chaos = chaos

    # -- fleet lifecycle -------------------------------------------------

    def _snapshot_dir(self, uid: int) -> Optional[str]:
        if self.snapshot_root is None:
            return None
        import os
        return os.path.join(self.snapshot_root, f"miner{uid}")

    def start(self) -> "ActorSwarm":
        if self._started:
            return self
        specs = [ActorSpec("miner", m.uid, m.stage, self.cfg, self.config,
                           self.train_cfg, self.store_address,
                           start_epoch=self.epoch,
                           behavior=self.faults.behaviors.get(m.uid),
                           snapshot_dir=self._snapshot_dir(m.uid),
                           chaos=self.chaos,
                           store_failover=self._failover)
                 for m in self.miners.values()]
        specs += [ActorSpec("validator", v.uid, -1, self.cfg, self.config,
                            self.train_cfg, self.store_address,
                            start_epoch=self.epoch,
                            chaos=self.chaos,
                            store_failover=self._failover)
                  for v in self.validators]
        self.supervisor.spawn(specs)
        self._started = True
        return self

    def check_liveness(self) -> None:
        """The EventDriver's await-loop hook: a dead child is an
        ``ActorDied`` now, not a watermark timeout two minutes later."""
        if self._started:
            self.supervisor.check()

    # -- chaos controls --------------------------------------------------

    def kill_miner(self, uid: int) -> None:
        """Hard-crash a miner process mid-run.  The next driver await
        surfaces ``ActorDied`` and graceful degradation re-plans the
        epoch around the casualty."""
        self.supervisor.kill(f"miner{uid}")

    def respawn_miner(self, uid: int) -> None:
        """Relaunch a killed miner.  Pins store GC retention at the
        miner's newest snapshot epoch (the keys its forward replay needs
        must survive), clears it from the dead census so the next plan
        schedules it, and crash-resumes the process."""
        name = f"miner{uid}"
        spec = self.supervisor.specs[name]
        snap_epoch = None
        if spec.snapshot_dir:
            snap_epoch = DiskSnapshotCache(spec.snapshot_dir).latest_epoch()
        rejoin = snap_epoch if snap_epoch is not None else self.epoch
        self.driver.pin_retention(name, rejoin)
        self.dead_uids.discard(uid)
        self.supervisor.respawn(name, start_epoch=rejoin)

    def fail_primary(self) -> None:
        """Kill the primary store server mid-run: every transport in the
        swarm (parent and children) reconnects, fails over to the warm
        standby and replays its pending requests there."""
        if self._standby is None:
            raise RuntimeError(
                "no warm standby: construct with store_standby=True")
        self._own_server.stop()
        self._own_server, self._standby = self._standby, None
        self.store_address = (str(self._own_server.address[0]),
                              int(self._own_server.address[1]))
        self._failover = ()

    def run_epoch(self):
        self.start()
        stats = self.driver.run_epoch(self)
        self._release_caught_up_pins()
        return stats

    def _release_caught_up_pins(self) -> None:
        """Retention pins hold GC only while the respawned miner is
        behind; once its heartbeat shows it reached the swarm's epoch
        the pin is dropped and the GC floors advance again."""
        for tag in list(getattr(self.driver, "_pins", {})):
            if tag not in self.supervisor.procs:
                self.driver.release_retention(tag)
                continue
            try:
                hb = self.supervisor.ping(tag)
            except (OSError, ConnectionError):
                continue
            if hb.epoch >= self.epoch:
                self.driver.release_retention(tag)

    def shutdown(self, stop_server: bool = True) -> None:
        """Stop the fleet (stop plan for the next epoch + health-endpoint
        stop pings), join, terminate stragglers, then stop the owned
        store server.  Idempotent."""
        from repro.api.messages import EpochPlanMsg
        if self._started:
            try:
                self.transport.publish(
                    EpochPlanMsg(self.epoch),
                    {"stop": True, "epoch": self.epoch},
                    actor="orchestrator")
            except (OSError, RuntimeError, ConnectionError):
                pass                 # store already down: fall through
            for name in self.supervisor.names:
                self.supervisor.stop(name)
            self.supervisor.join_all(timeout=10.0)
            self.supervisor.terminate_all()
            self._started = False
        if self._own_server is not None and stop_server:
            self._own_server.stop()
            self._own_server = None
        if self._standby is not None and stop_server:
            self._standby.stop()
            self._standby = None
        self.transport.close()

    def __enter__(self) -> "ActorSwarm":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()
