"""Driver of the swarm cells: ``Swarm.run_epoch`` of the in-process swarm.

Set-up builds one swarm, gives it weights made from the seed and the
benchmark's own batches, and runs one whole epoch through the window's own
call.  That epoch warms every shape the window uses, and its first ticks
are what the reference follows.  The window then runs whole epochs: one
starts only while the last one's duration fits in what is left.

Readings of the set-up epoch, taken as it runs:
  losses    the loss of each of the first three ticks
  grad1     per-leaf norms of each trained miner's first gradient, worked
            out from AdamW's first moment after one update (mu / (1 - b1))
  change3   per-leaf norms of each miner's parameter change after three
            ticks
  anchor    (timelines that merge) per-leaf norms of each stage anchor's
            change after the epoch's merge and outer step
  rejects   (timelines that validate) items the validator rejected
  planted   (timelines that validate) items a validator passed where every
            upload it checked was corrupted (``planted_passed``)
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from bench import models
from bench.lib import compare, flops
from bench.lib import reference as ref
from bench.lib.spans import Spans
from bench.lib.tokens import TokenBatches

FOLLOWED = 3        # ticks the losses, gradients and changes are read over


@jax.jit
def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def change_norms(a, b):
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                    - y.astype(jnp.float32))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


def keyed(owner, norms) -> dict:
    return {f"{owner}/{i}": float(v) for i, v in enumerate(np.asarray(norms))}


class TimedPhase:
    """One program phase inside a benchmark span.  It blocks on the miners'
    parameters at its end, so the span holds the device work it queued.
    ``before``, where set, runs once ahead of the phase, outside the span."""

    def __init__(self, phase, spans: Spans, on_done=None):
        self.phase, self.spans, self.on_done = phase, spans, on_done
        self.name = phase.name
        self.before = None

    def run(self, swarm, state) -> None:
        if self.before is not None:
            self.before(swarm, state)
            self.before = None
        with self.spans.span(self.name):
            self.phase.run(swarm, state)
            jax.block_until_ready([m.params for m in swarm.miners.values()])
        if self.on_done is not None:
            self.on_done(state)


def tracked(swarm, state) -> list:
    """One miner of every stage that trained this epoch, by lowest uid."""
    return [next(u for u, m in sorted(swarm.miners.items())
                 if m.stage == s and m.work_log and u in state.snapshots)
            for s in range(swarm.config.n_stages)]


def warm_validator(swarm, state) -> None:
    """Replay one miner of every stage once, in set-up: the validator tracks
    a miner drawn at random, and the window must find the programs of
    either stage's replay built."""
    v = swarm.validators[0]
    for uid in tracked(swarm, state):
        v.validate_epoch(swarm.miners[uid], state.snapshots[uid], state.epoch,
                         0.0, state.labels_for,
                         max_items=swarm.config.validate_max_items)


class PlantedUploads:
    """The store as a validator reads it, with the uploads under ``keys``
    corrupted: noise of half their norm added, drawn from the seed, as from
    a miner whose uploads its stage did not compute."""

    def __init__(self, transport, keys, seed: int):
        self.transport, self.keys = transport, set(keys)
        self.rng = np.random.default_rng(seed)

    def __getattr__(self, name):
        return getattr(self.transport, name)

    def get(self, key: str, actor: str = "?"):
        x = self.transport.get(key, actor=actor)
        if key not in self.keys:
            return x
        x = np.asarray(x, np.float32)
        noise = self.rng.standard_normal(x.shape, np.float32)
        return x + noise * (0.5 * np.linalg.norm(x) / np.linalg.norm(noise))


def planted_passed(swarm, state, seed: int) -> tuple[int, int]:
    """(checked, passed) of a fresh validator, with its own ledger, that
    replays the miners ``warm_validator`` replays while every upload it
    checks is corrupted.  A sound validator passes none of them."""
    from repro.core.incentives import IncentiveLedger
    from repro.runtime.validator import Validator
    checked = passed = 0
    n_items = swarm.config.validate_max_items
    for uid in tracked(swarm, state):
        miner = swarm.miners[uid]
        keys = [item.out_key for item in miner.work_log[:n_items]]
        v = Validator(swarm.validators[0].uid,
                      PlantedUploads(swarm.transport, keys, seed),
                      IncentiveLedger(swarm.config.gamma_hours))
        res = v.validate_epoch(miner, state.snapshots[uid], state.epoch, 0.0,
                               state.labels_for, max_items=n_items)
        checked, passed = checked + res.checked, passed + res.passed
    return checked, passed


class HookedBatches(TokenBatches):
    """The benchmark's batches, with a call-back before batch ``step`` is
    handed out: by then every tick before ``step`` has updated its miners."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.hooks: dict = {}

    def batch(self, step: int) -> dict:
        hook = self.hooks.pop(step, None)
        if hook is not None:
            hook()
        return super().batch(step)


class SwarmCell:
    end_to_end = "swarm_tokens_per_s"

    def __init__(self, ctx: dict):
        self.ctx = ctx
        self.seed = ctx["seed"]
        self.m = ctx["config"]["model"]
        self.opt = ctx["config"]["optimizer"]
        self.family_name = ctx["config"]["family"]
        self.family = models.get(self.family_name)
        self.t = ctx["traffic"]
        self.merges = "sync" in self.t["phases"]
        self.validates = "validation" in self.t["phases"]
        self.spans = Spans()
        self.prog: dict = {}
        # planted faults of the check, as the reference put in the
        # program's place
        self.faults = {"half_batch": {"half": True}}
        assert self.t["ticks_per_epoch"] > FOLLOWED, self.t

    def batches(self, cls=TokenBatches):
        t = self.t
        return cls(self.seed, self.m["vocab_size"], t["batch_size"],
                   t["seq_len"], t["zipf_exponent"])

    # ------------------------------------------------------------------

    def setup(self) -> None:
        from repro.api import Swarm, SwarmConfig
        from repro.api import phases as ph
        from repro.configs.base import TrainConfig
        from repro.core import diloco

        t, opt = self.t, self.opt
        config = SwarmConfig(
            n_stages=t["n_stages"], miners_per_stage=t["miners_per_stage"],
            validators=t["validators"], inner_steps=t["ticks_per_epoch"],
            b_min=t["b_min"], quorum_frac=t["quorum_frac"],
            batch_size=t["batch_size"], seq_len=t["seq_len"],
            bottleneck_dim=t["bottleneck_dim"], share_codec=t["share_codec"],
            sync_mode=t["sync_mode"],
            validate_max_items=t["validate_max_items"],
            retain_epochs=t["retain_epochs"], outer_lr=t["outer_lr"],
            outer_momentum=t["outer_momentum"], seed=self.seed % 2**31)
        train_cfg = TrainConfig(
            lr=opt["lr"], warmup_steps=opt["warmup_steps"],
            weight_decay=opt["weight_decay"], beta1=opt["beta1"],
            beta2=opt["beta2"], eps=opt["eps"])
        kinds = {"training": ph.TrainingPhase,
                 "validation": ph.ValidationPhase,
                 "sharing": ph.SharingPhase, "sync": ph.SyncPhase,
                 "reduce_audit": ph.ReduceAuditPhase}
        self._records = []
        phases = [TimedPhase(kinds[n](), self.spans,
                             self._keep_records if n == "training" else None)
                  for n in t["phases"]]
        swarm = Swarm.create(
            self.family.program_config(self.ctx["config_name"], self.m),
            config, phases=phases, train_cfg=train_cfg)

        # the benchmark's weights and batches in place of the program's
        self.shapes = [jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), a)
            for a in swarm.anchors]
        w0 = ref.make_weights(self.seed, self.shapes, self.family.init_leaf)
        for s, w in enumerate(w0):
            swarm.anchors[s] = w
            swarm.outer[s] = diloco.outer_init(w)
        for miner in swarm.miners.values():
            miner.params = jax.tree.map(jnp.copy, w0[miner.stage])
        corpus = self.batches(HookedBatches)
        swarm.corpus = corpus

        grad1, change3 = {}, {}

        def after_one():
            for uid, miner in swarm.miners.items():
                if int(miner.inner_step) == 1:
                    grad1[uid] = leaf_norms(miner.opt_state["mu"])

        def after_three():
            for uid, miner in swarm.miners.items():
                change3[uid] = change_norms(miner.params, w0[miner.stage])

        corpus.hooks = {1: after_one, FOLLOWED: after_three}
        self.check_s = 0.0

        def before_validation(swarm, state):
            warm_validator(swarm, state)
            t0 = time.perf_counter()
            self._planted = planted_passed(swarm, state, self.seed)
            self.check_s = time.perf_counter() - t0

        for p in phases:
            if p.name == "validation":
                p.before = before_validation
        t0 = time.perf_counter()
        stats = swarm.run_epoch()
        self._block(swarm)
        self.warm_epoch_s = time.perf_counter() - t0

        b1 = opt["beta1"]
        self.routing = [tuple(int(u) for u in r.pathway)
                        for r in self._records]
        self.prog = {
            "losses": [float(r.loss) for r in self._records[:FOLLOWED]],
            "grad1": {k: v / (1 - b1) for uid, n in grad1.items()
                      for k, v in keyed(uid, n).items()},
            "change3": {k: v for uid, n in change3.items()
                        for k, v in keyed(uid, n).items()},
            "stalled": stats.stalled_ticks,
        }
        if self.merges:
            self.prog["anchor"] = {
                k: v for s in range(len(w0))
                for k, v in keyed(s, change_norms(swarm.anchors[s],
                                                  w0[s])).items()}
            self.prog["merged"] = stats.merged_stages
        if self.validates:
            self.prog["rejects"] = sum(r.checked - r.passed
                                       for r in stats.validation)
            self.prog["checked"] = sum(r.checked for r in stats.validation)
            self.prog["planted"] = self._planted
        self.swarm = swarm
        self.stage_of = {u: m.stage for u, m in swarm.miners.items()}
        self.vector_len = [int(sum(np.prod(x.shape) for x in
                                   jax.tree.leaves(shp)))
                           for shp in self.shapes]

    def _keep_records(self, state) -> None:
        self._records = list(state.records)

    @staticmethod
    def _block(swarm) -> None:
        jax.block_until_ready(([m.params for m in swarm.miners.values()],
                               swarm.anchors))

    # ------------------------------------------------------------------

    def window(self, seconds: float) -> dict:
        swarm, t = self.swarm, self.t
        T, n_stages = t["ticks_per_epoch"], t["n_stages"]
        bytes0 = swarm.transport.traffic_report()["total_bytes"]
        epochs = []
        last = self.warm_epoch_s
        start = time.perf_counter()
        self.window_start_ns = time.perf_counter_ns()
        with self.spans.span("window"):
            while True:
                left = seconds - (time.perf_counter() - start)
                if epochs and last > left:
                    break
                t0 = time.perf_counter()
                with self.spans.span("epoch"):
                    stats = swarm.run_epoch()
                    self._block(swarm)
                last = time.perf_counter() - t0
                epochs.append((last, stats.stalled_ticks,
                               stats.merged_stages))
        window_s = time.perf_counter() - start
        attempted = T * len(epochs)
        failed = 0
        for _, stalled, merged in epochs:
            if self.merges and merged < n_stages:
                failed += T
            else:
                failed += stalled
        tokens = (attempted - failed) * t["batch_size"] * t["seq_len"]
        epoch_s = sum(e[0] for e in epochs)
        self.readings = {
            "tokens": tokens, "epochs": len(epochs), "trained_seconds": epoch_s,
            "ticks": attempted - failed,
            "store_bytes": swarm.transport.traffic_report()["total_bytes"]
            - bytes0,
        }
        return {"attempted": attempted, "failed": failed,
                "metrics": {self.end_to_end: tokens / epoch_s}}

    def context(self) -> dict:
        """What the per-layer readers need besides the trace."""
        m, t = self.m, self.t
        n_layers = m["num_hidden_layers"]
        return dict(
            self.readings, spans=self.spans.rows,
            window_start_ns=self.window_start_ns, model=m,
            flops_per_token=flops.train_flops_per_token(
                m, self.family, n_layers, t["seq_len"], t["bottleneck_dim"],
                t["n_stages"] - 1),
            attention=self.family.attention_shape(m, t["batch_size"],
                                                  t["seq_len"]),
            stage_vector_len=self.vector_len)

    # ------------------------------------------------------------------

    def release(self) -> None:
        del self.swarm
        gc.collect()

    def check(self, limits: dict) -> list:
        if self.prog["stalled"]:
            return compare.rows({}, limits)
        got = self.reference("f32", self.routing)
        return compare.rows(self.numbers(self.prog, got), limits)

    def numbers(self, prog: dict, want: dict) -> dict:
        """Every number the check can compare; the cell's limits file
        names the ones it does."""
        out = {"loss1_gap": compare.loss_gap(prog["losses"][:1],
                                             want["losses"][:1]),
               "loss_gap": compare.loss_gap(prog["losses"], want["losses"])}
        # (stage, leaf) pairs whose first gradient is nought, by owner key
        nought = {(self.stage_of[int(k.split("/")[0])], k.split("/")[1])
                  for k in compare.nought_leaves(want["grad1"])}
        left_out = {
            "grad1": {f"{u}/{i}" for u, s in self.stage_of.items()
                      for t, i in nought if s == t},
            "anchor": {f"{s}/{i}" for s, i in nought}}
        left_out["change3"] = left_out["grad1"]
        parts = ["grad1", "change3"] + (["anchor"] if self.merges else [])
        for part in parts:
            out[part + "_gap"] = compare.worst_leaf(prog[part], want[part],
                                                    left_out[part])
        if "rejects" in prog:
            out["validator_rejects"] = float(prog["rejects"]) \
                if prog["checked"] else float("nan")
            checked, passed = prog["planted"]
            out["validator_planted_passed"] = float(passed) \
                if checked else float("nan")
        return out

    def reference(self, mode: str, routing: list | None = None,
                  half: bool = False) -> dict:
        """Follow ``routing`` (one tuple of miner uids per tick; the set-up
        epoch's by default) with the plain reference in ``mode``: the first
        three ticks for the losses, gradients and changes, and, where the
        timeline merges, the whole epoch and its merge.  ``half`` is a
        planted fault: every tick trains on the first half of its batch
        only."""
        t, opt = self.t, self.opt
        routing = self.routing if routing is None else routing
        stage_of = self.stage_of
        w0 = ref.make_weights(self.seed, self.shapes, self.family.init_leaf)
        params = {u: w0[s] for u, s in stage_of.items()}
        states = {u: ref.adamw_init(w0[s]) for u, s in stage_of.items()}
        corpus = self.batches()
        mkey = tuple(sorted(self.m.items()))
        okey = tuple(sorted(self.opt.items()))
        follow = routing if self.merges else routing[:FOLLOWED]
        out = {"losses": []}
        for tick, uids in enumerate(follow):
            b = corpus.batch(tick)
            if half:
                b = {k: v[: len(v) // 2] for k, v in b.items()}
            loss, ps, ss = ref.train_tick(
                tuple(params[u] for u in uids), tuple(states[u] for u in uids),
                jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]),
                m=mkey, opt=okey, mode=mode, trained=(True,) * len(uids),
                family=self.family_name)
            for u, p, s in zip(uids, ps, ss):
                params[u], states[u] = p, s
            if tick < FOLLOWED:
                out["losses"].append(float(loss))
            if tick == 0:
                out["grad1"] = {
                    k: v / (1 - opt["beta1"]) for u in uids
                    for k, v in keyed(u, leaf_norms(states[u]["mu"])).items()}
            if tick == FOLLOWED - 1:
                moved = {u for r in routing[:FOLLOWED] for u in r}
                out["change3"] = {
                    k: v for u in sorted(moved)
                    for k, v in keyed(u, change_norms(
                        params[u], w0[stage_of[u]])).items()}
        if self.merges:
            out["anchor"] = self._merge(routing, params, w0)
        return out

    def _merge(self, routing: list, params: dict, w0: list) -> dict:
        """The epoch's merge: every stage with two or more qualifying
        miners averages their int8 round-tripped weight vectors, and the
        anchor takes one outer Nesterov step towards that average."""
        t = self.t
        done = {u: 0 for u in self.stage_of}
        for uids in routing:
            for u in uids:
                done[u] += 1
        qualifying = [u for u, n in done.items() if n >= t["b_min"]]
        quorum = len(qualifying) >= max(1, int(len(done) * t["quorum_frac"]))
        out = {}
        for s in range(t["n_stages"]):
            anchor, unravel = ravel_pytree(w0[s])
            qual = [u for u in qualifying if self.stage_of[u] == s]
            new = anchor
            if quorum and len(qual) >= 2:
                avg = sum(ref.int8_roundtrip(ravel_pytree(params[u])[0],
                                             block=t["share_block"])
                          for u in qual) / len(qual)
                new = ref.outer_nesterov(anchor, avg, t["outer_lr"],
                                         t["outer_momentum"])
            out.update(keyed(s, change_norms(unravel(new), w0[s])))
        return out


def build(ctx: dict) -> SwarmCell:
    return SwarmCell(ctx)
