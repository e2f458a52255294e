"""Butterfly All-Reduce (paper §5): pair-indexed shards, 2x redundancy,

O(1) per-miner bandwidth, agreement-matrix verification, graceful failures.

Construction (§5, eqs. 1-2): for N miners on one layer, enumerate all
P = {(i,j) : i<j} pairs (|P| = N(N-1)/2), apply a seeded random bijection
f : P -> {0..|P|-1}; shard s of the flattened parameter space is *assigned*
to the two miners of pair f^-1(s).  Each assignee downloads shard s from all
N miners, averages, re-uploads.  Every shard therefore has exactly two
independent reducers:

* agreement: the two copies are compared (cosine similarity) — a deceptive
  reducer is exposed by every partner it shares a shard with (Fig 7a);
* fault tolerance: a shard is lost only if BOTH assignees fail, so
  |P_valid| = C(N,2) - C(k,2) with k faulty miners (Fig 7b);
* bandwidth: per miner = upload W + download 2W + upload 2W/N + download W
  = 4W + 2W/N — O(1) in N (§5.3), vs N*W for a central merger.

Three implementations share the math:
  * ``ButterflyPlan`` + ``reduce_shards`` — the reduce run centrally over
    in-memory vectors: the *golden oracle* the store-and-forward path must
    reproduce to float equality.
  * ``ButterflyExecutor`` — the reduce as per-miner store-and-forward
    actions over a ``Transport``: every shard upload, reduce download and
    reduced-copy re-upload crosses the wire under the acting miner's link,
    so ``SimulatedNetworkTransport`` byte accounting reproduces the §5.3
    closed form 4W + 2W/N, and validators can audit the reduce from store
    artifacts alone (``store_agreement``).  Needs KeySchema v2.
  * ``butterfly_all_reduce_mesh`` — the on-mesh equivalent for TPU pods:
    redundancy-2 reduce-scatter (+shifted copy) + agreement compare +
    all-gather, expressed in shard_map collectives.  Used by the DiLoCo
    outer merge on the ``pod``/``data`` axis.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import cdiv, span
from repro.core import compression
from repro.kernels import ops


# ---------------------------------------------------------------------------
# Plan construction (paper eqs. 1-2)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ButterflyPlan:
    n_miners: int
    pairs: tuple[tuple[int, int], ...]      # shard s -> (miner_i, miner_j)
    vector_len: int
    # shard boundaries snap to multiples of ``align`` (except the vector
    # end).  Sharded sync sets align to the wire codec's quantization block
    # so per-shard int8 codes are bit-identical to slices of the full
    # vector's codes — the dense-vs-sharded parity contract.
    align: int = 1

    @property
    def n_shards(self) -> int:
        return len(self.pairs)

    def shard_bounds(self, s: int) -> tuple[int, int]:
        """Near-equal contiguous slices of the flattened parameter vector;
        with ``align > 1``, near-equal in whole blocks (trailing shards may
        be empty when the vector has fewer blocks than shards)."""
        if self.align == 1:
            base = self.vector_len // self.n_shards
            extra = self.vector_len % self.n_shards
            lo = s * base + min(s, extra)
            hi = lo + base + (1 if s < extra else 0)
            return lo, hi
        blocks = cdiv(self.vector_len, self.align)
        base = blocks // self.n_shards
        extra = blocks % self.n_shards
        blo = s * base + min(s, extra)
        bhi = blo + base + (1 if s < extra else 0)
        return (min(blo * self.align, self.vector_len),
                min(bhi * self.align, self.vector_len))

    def shards_of(self, miner: int) -> list[int]:
        """Shard indices assigned to ``miner`` (one per partner: N-1 shards)."""
        return [s for s, (i, j) in enumerate(self.pairs) if miner in (i, j)]


def make_plan(n_miners: int, vector_len: int, seed: int = 0,
              align: int = 1) -> ButterflyPlan:
    assert n_miners >= 2
    pairs = list(itertools.combinations(range(n_miners), 2))
    rng = np.random.RandomState(seed)
    rng.shuffle(pairs)                       # the random bijection f
    return ButterflyPlan(n_miners, tuple(tuple(p) for p in pairs),
                         vector_len, align)


# ---------------------------------------------------------------------------
# Fault / bandwidth math (paper §5.2-5.3)
# ---------------------------------------------------------------------------


def valid_shard_fraction(n: int, k: int) -> float:
    """p_valid = 1 - k(k-1) / (N(N-1)) — fraction of shards still reduced

    correctly with k faulty miners (Fig 7b)."""
    if n < 2:
        return 0.0
    return 1.0 - (k * (k - 1)) / (n * (n - 1))


def transfer_volume(n_miners: int, w_bytes: float) -> dict:
    """Per-miner and total traffic; the paper's 4W + 2W/N vs central N*W."""
    per_miner = 4 * w_bytes + 2 * w_bytes / n_miners
    return {
        "per_miner_bytes": per_miner,
        "total_bytes": per_miner * n_miners,
        "central_merger_bytes": n_miners * w_bytes + 3,   # paper's comparison
        "n_miners": n_miners,
    }


# ---------------------------------------------------------------------------
# Exact simulation (runtime path)
# ---------------------------------------------------------------------------


def reduce_shards(
    plan: ButterflyPlan,
    uploads: dict[int, np.ndarray],          # miner -> full flattened vector
    reducer_ok: Optional[Sequence[bool]] = None,   # reducer miner alive?
    tamper: Optional[dict[int, float]] = None,     # miner -> additive noise
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Run the full butterfly reduce.

    Returns (merged vector, shard_valid (n_shards,), agreement (n_shards, 2)
    reducer ids with per-shard copy agreement encoded via ``shard_agree``).
    Uses ``kernels.ops.shard_merge`` (masked mean) for each shard reduction.
    """
    n = plan.n_miners
    reducer_ok = list(reducer_ok) if reducer_ok is not None else [True] * n
    tamper = tamper or {}
    present = sorted(uploads.keys())
    merged = np.zeros(plan.vector_len, np.float32)
    shard_valid = np.zeros(plan.n_shards, bool)
    shard_agree = np.ones(plan.n_shards, bool)

    # stack uploads once; missing miners -> masked out
    stacked = np.stack([
        np.asarray(uploads[m], np.float32) if m in uploads
        else np.zeros(plan.vector_len, np.float32)
        for m in range(n)])
    valid_mask = np.array([m in uploads for m in range(n)])

    for s, (i, j) in enumerate(plan.pairs):
        lo, hi = plan.shard_bounds(s)
        if hi == lo:
            shard_valid[s] = True
            continue
        copies = []
        for reducer in (i, j):
            if not reducer_ok[reducer]:
                continue
            block = jnp.asarray(stacked[:, lo:hi])
            mean = np.asarray(ops.shard_merge(block, jnp.asarray(valid_mask)))
            if reducer in tamper:
                mean = mean + tamper[reducer]
            copies.append((reducer, mean))
        if not copies:
            shard_valid[s] = False          # both assignees down: shard lost
            continue
        shard_valid[s] = True
        if len(copies) == 2:
            a, b = copies[0][1], copies[1][1]
            shard_agree[s] = bool(np.allclose(a, b, rtol=1e-4, atol=1e-5))
        merged[lo:hi] = copies[0][1]        # first surviving copy wins
    return merged, shard_valid, shard_agree


def agreement_matrix(
    plan: ButterflyPlan,
    reduced_copies: dict[tuple[int, int], np.ndarray],   # (shard, reducer) -> copy
) -> np.ndarray:
    """(N, N) matrix: fraction of shared shards on which each miner pair's

    reduced copies agree (Fig 7a; off-consensus rows expose deceivers)."""
    n = plan.n_miners
    agree = np.full((n, n), np.nan)
    for s, (i, j) in enumerate(plan.pairs):
        a = reduced_copies.get((s, i))
        b = reduced_copies.get((s, j))
        if a is None or b is None:
            continue
        ok = float(np.allclose(a, b, rtol=1e-4, atol=1e-5))
        agree[i, j] = agree[j, i] = ok
    np.fill_diagonal(agree, 1.0)
    return agree


def reduce_with_copies(
    plan: ButterflyPlan,
    uploads: dict[int, np.ndarray],
    tamper: Optional[dict[int, float]] = None,
) -> dict[tuple[int, int], np.ndarray]:
    """Each reducer's copy of each assigned shard (input to agreement_matrix)."""
    n = plan.n_miners
    tamper = tamper or {}
    stacked = np.stack([
        np.asarray(uploads[m], np.float32) if m in uploads
        else np.zeros(plan.vector_len, np.float32) for m in range(n)])
    valid_mask = jnp.asarray(np.array([m in uploads for m in range(n)]))
    out = {}
    for s, (i, j) in enumerate(plan.pairs):
        lo, hi = plan.shard_bounds(s)
        block = jnp.asarray(stacked[:, lo:hi])
        base = np.asarray(ops.shard_merge(block, valid_mask))
        for reducer in (i, j):
            copy = base + tamper.get(reducer, 0.0)
            out[(s, reducer)] = copy
    return out


# ---------------------------------------------------------------------------
# Store-and-forward execution over a Transport (KeySchema v2, §5.1-5.3)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardAssignment:
    """One unit of reducer work: download every miner's copy of ``shard``,
    masked-merge, re-upload the reduced copy."""
    shard: int
    lo: int
    hi: int
    upload_keys: tuple[str, ...]     # plan order: one key per plan index
    reduced_key: str
    reducer_uid: int


class ButterflyExecutor:
    """Drives the butterfly reduce as store-and-forward actions over a
    ``Transport`` — nothing is merged centrally.

    Three steps, each charged to the acting peer's link so the §5.3
    closed form falls out of the byte accounting:

      1. ``upload_vector``   each miner splits its flat weight vector on
                             the plan's shard bounds and uploads every
                             shard (``W`` up per miner),
      2. ``reduce_one``      each reducer downloads all N copies of an
                             assigned shard (``2W`` down across its N-1
                             shards), masked-merges them with the
                             ``kernels.ops.shard_merge`` dispatch, and
                             re-uploads its reduced copy (``2W/N`` up),
      3. ``collect``         the anchor assembly reads the redundant
                             reduced copies back (first surviving copy per
                             shard wins, exactly like ``reduce_shards``).

    Shard uploads ride ``codec`` (the sharing stage's wire codec, int8 by
    default).  Reduced copies always ride fp32: they are the consensus
    artifact the anchor is assembled from, quantizing them a second time
    would compound the codec error, and they are only ``2W/N`` of traffic.
    With ``plan.align`` set to the codec's quantization block, per-shard
    codes are bit-identical to slices of a whole-vector encode, so the
    assembled anchor equals the dense oracle's to float equality.

    The transport's schema must be KeySchema v2 (minting a shard key from
    a v1 schema raises).
    """

    def __init__(self, plan: ButterflyPlan, transport, *, epoch: int,
                 stage: int, uids: Sequence[int], codec: str = "none"):
        assert len(uids) == plan.n_miners, (len(uids), plan.n_miners)
        self.plan = plan
        self.transport = transport
        self.epoch = epoch
        self.stage = stage
        self.uids = tuple(uids)              # plan index -> real miner uid
        self.codec = codec
        # agreement matrix of the last collect() (plan-index-indexed) —
        # collect computes it for consensus weighting; callers reuse it
        # instead of re-comparing every copy
        self.last_agreement: Optional[np.ndarray] = None

    # -- key minting (the only schema touchpoints) -----------------------

    def upload_key(self, idx: int, shard: int) -> str:
        return self.transport.schema.shard_upload(
            self.epoch, self.stage, self.uids[idx], shard)

    def reduced_key(self, shard: int, idx: int) -> str:
        return self.transport.schema.shard_reduced(
            self.epoch, self.stage, shard, self.uids[idx])

    # -- step 1: sharded upload (actor = the uploading miner) ------------

    def upload_vector(self, idx: int, vector: np.ndarray,
                      actor: str) -> list[str]:
        """Publish miner ``idx``'s flat weight vector as per-shard payloads
        (empty shards are skipped); returns the minted keys."""
        from repro.api.messages import ShardUploadMsg
        with span("share.upload"):
            vec = jnp.asarray(vector, jnp.float32)
            assert vec.shape[0] == self.plan.vector_len, \
                (vec.shape, self.plan.vector_len)
            keys = []
            for s in range(self.plan.n_shards):
                lo, hi = self.plan.shard_bounds(s)
                if hi == lo:
                    continue
                msg = ShardUploadMsg(self.epoch, self.stage, self.uids[idx],
                                     s, codec=self.codec)
                payload = compression.encode(vec[lo:hi], self.codec)
                self.transport.publish(msg, payload, actor=actor)
                keys.append(msg.key(self.transport.schema))
            return keys

    # -- step 2: reduce (actor = the assigned reducer) -------------------

    def assignments_for(self, idx: int) -> list[ShardAssignment]:
        """The N-1 shard reductions the plan assigns to miner ``idx``."""
        out = []
        for s in self.plan.shards_of(idx):
            lo, hi = self.plan.shard_bounds(s)
            if hi == lo:
                continue
            out.append(ShardAssignment(
                s, lo, hi,
                tuple(self.upload_key(i, s)
                      for i in range(self.plan.n_miners)),
                self.reduced_key(s, idx),
                self.uids[idx]))
        return out

    def reduce_one(self, assignment: ShardAssignment, actor: str,
                   tamper: float = 0.0) -> np.ndarray:
        """Download every miner's copy of one shard, masked-merge, upload
        the reduced copy.  ``tamper`` is the fault-injection hook: a
        deceptive reducer adds a constant offset after the merge (same
        semantics as ``reduce_with_copies``)."""
        from repro.api.messages import ShardReducedMsg
        with span("sync.reduce"):
            n = self.plan.n_miners
            width = assignment.hi - assignment.lo
            blocks = np.zeros((n, width), np.float32)
            valid = np.zeros((n,), bool)
            for i, key in enumerate(assignment.upload_keys):
                if not self.transport.exists(key):
                    continue             # miner never uploaded: mask out
                payload = self.transport.get(key, actor=actor)
                blocks[i] = np.asarray(compression.decode(payload, width))
                valid[i] = True
            mean = np.asarray(ops.shard_merge(jnp.asarray(blocks),
                                              jnp.asarray(valid)))
            if tamper:
                mean = mean + np.float32(tamper)
            msg = ShardReducedMsg(self.epoch, self.stage, assignment.shard,
                                  assignment.reducer_uid)
            self.transport.publish(msg, compression.encode(mean, "none"),
                                   actor=actor)
            return mean

    def run_reducer(self, idx: int, actor: str,
                    tamper: float = 0.0) -> list[ShardAssignment]:
        """All of miner ``idx``'s reduce work; returns what was done (the
        runtime miner logs it for validator replay)."""
        done = []
        for a in self.assignments_for(idx):
            self.reduce_one(a, actor=actor, tamper=tamper)
            done.append(a)
        return done

    # -- step 3: anchor assembly from the redundant copies ---------------

    def collect(self, actor: str = "orchestrator") -> tuple[
            np.ndarray, np.ndarray, dict[tuple[int, int], np.ndarray]]:
        """Assemble the merged vector from the store's reduced copies.

        Returns (merged, shard_valid, copies) with ``copies`` keyed by
        (shard, plan index) — the same structure ``reduce_with_copies``
        returns, so ``agreement_matrix`` applies unchanged.  A shard is
        lost only when *neither* assignee uploaded a copy (Fig 7b).

        Copy selection is consensus-weighted: honest reducers of a shard
        produce bit-identical copies (same store inputs, same merge), so
        when the two copies *disagree* the assembly prefers the copy from
        the reducer with the higher mean agreement across all its shards —
        a single tamperer (out of consensus with every partner, Fig 7a)
        cannot poison the anchor as long as its partner is honest.  Only a
        shard whose *both* assignees are dishonest, or whose only
        surviving copy is tampered, degrades."""
        with span("sync.collect"):
            copies: dict[tuple[int, int], np.ndarray] = {}
            for s, (i, j) in enumerate(self.plan.pairs):
                lo, hi = self.plan.shard_bounds(s)
                if hi == lo:
                    continue
                for r in (i, j):
                    key = self.reduced_key(s, r)
                    if not self.transport.exists(key):
                        continue
                    payload = self.transport.get(key, actor=actor)
                    copies[(s, r)] = np.asarray(
                        compression.decode(payload, hi - lo))
            # per-reducer consensus: mean agreement over pairs with both
            # copies
            agree = agreement_matrix(self.plan, copies)
            self.last_agreement = agree
            n = self.plan.n_miners
            consensus = np.array([
                np.nanmean(agree[m][np.arange(n) != m])
                if np.any(~np.isnan(agree[m][np.arange(n) != m])) else 1.0
                for m in range(n)])
            merged = np.zeros(self.plan.vector_len, np.float32)
            shard_valid = np.zeros(self.plan.n_shards, bool)
            for s, (i, j) in enumerate(self.plan.pairs):
                lo, hi = self.plan.shard_bounds(s)
                if hi == lo:
                    shard_valid[s] = True
                    continue
                present = [r for r in (i, j) if (s, r) in copies]
                if not present:
                    continue             # both assignees down: lost
                best = max(present, key=lambda r: (consensus[r], -r))
                merged[lo:hi] = copies[(s, best)]
                shard_valid[s] = True
            return merged, shard_valid, copies


def store_agreement(transport, epoch: int, stage: int,
                    actor: str = "?") -> tuple[list[int], np.ndarray]:
    """Rebuild the Fig 7a agreement evidence purely from wire artifacts.

    Walks the store's ``weights/ep{E}/s{S}`` prefix for ``shard_reduced``
    keys, pairs up each shard's two redundant copies and compares them —
    no plan, miner state or uploader cooperation needed: shard identity and
    the reducer uids are in the keys themselves.  Returns (uids, matrix)
    with the matrix indexed by position in the sorted uid list; a tampering
    reducer shows a ~0 row against every partner."""
    schema = transport.schema
    by_shard: dict[int, list[tuple[int, str]]] = {}
    for key in transport.keys(schema.stage_weights_prefix(epoch, stage)):
        try:
            parsed = schema.parse(key)
        except ValueError:
            continue                         # foreign key kinds: not ours
        if parsed.kind != "shard_reduced":
            continue
        # the walk is a plain string-prefix match, so stage 1's prefix
        # also catches stage 12/13/... keys — filter on the parsed fields
        if (parsed.fields["epoch"] != epoch
                or parsed.fields["stage"] != stage):
            continue
        by_shard.setdefault(parsed.fields["shard"], []).append(
            (parsed.fields["reducer"], key))
    uids = sorted({uid for entries in by_shard.values()
                   for uid, _ in entries})
    pos = {u: i for i, u in enumerate(uids)}
    agree = np.full((len(uids), len(uids)), np.nan)
    for entries in by_shard.values():
        if len(entries) != 2:
            continue                         # copy lost: nothing to compare
        (ua, ka), (ub, kb) = sorted(entries)
        with span("audit.compare"):
            a = np.asarray(compression.decode(transport.get(ka,
                                                            actor=actor)))
            b = np.asarray(compression.decode(transport.get(kb,
                                                            actor=actor)))
            ok = float(np.allclose(a, b, rtol=1e-4, atol=1e-5))
        agree[pos[ua], pos[ub]] = agree[pos[ub], pos[ua]] = ok
    if len(uids):
        np.fill_diagonal(agree, 1.0)
    return uids, agree


# ---------------------------------------------------------------------------
# On-mesh butterfly (TPU pods): redundancy-2 reduce-scatter + all-gather
# ---------------------------------------------------------------------------


def butterfly_all_reduce_mesh(x: jax.Array, axis: str, mesh,
                              in_spec=None, redundancy: int = 2):
    """Mean-all-reduce of ``x`` along mesh axis ``axis`` with butterfly-style

    redundancy: two independent reduce-scatters over shifted shard
    assignments produce two copies of every shard on different devices; the
    copies are cross-checked (ppermute + compare) before the all-gather.
    Returns (reduced x, agreement fraction scalar).

    Bandwidth per device: 2 * (W/N reduce-scatter) + W all-gather + W/N
    permute ≈ the paper's 4W + 2W/N counted one-sided on uploads+downloads.
    """
    n = mesh.shape[axis]
    in_spec = in_spec if in_spec is not None else jax.sharding.PartitionSpec()
    other_axes = tuple(a for a in mesh.axis_names if a != axis)

    def body(v):
        size = v.size
        flat = v.reshape(-1)
        pad = (-size) % n
        flat = jnp.pad(flat, (0, pad))
        shard_len = flat.shape[0] // n
        # copy A: canonical assignment (device d reduces shard d)
        copy_a = jax.lax.psum_scatter(flat, axis, scatter_dimension=0,
                                      tiled=True) / n
        # copy B: shifted assignment (device d reduces shard d+1 mod n)
        copy_b = jax.lax.psum_scatter(jnp.roll(flat, -shard_len), axis,
                                      scatter_dimension=0, tiled=True) / n
        # align copy B onto shard d's canonical reducer and cross-check:
        # device d-1 holds shard d in copy_b -> send i -> i+1
        perm = [(i, (i + 1) % n) for i in range(n)]
        copy_b_aligned = jax.lax.ppermute(copy_b, axis, perm)
        agree = jnp.mean((jnp.abs(copy_a - copy_b_aligned)
                          <= 1e-3 * (jnp.abs(copy_a) + 1e-6)).astype(jnp.float32))
        agree = jax.lax.pmean(agree, axis)
        merged = jax.lax.all_gather(copy_a, axis, axis=0, tiled=True)
        merged = merged[:size].reshape(v.shape)
        return merged, agree

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(in_spec,),
        out_specs=(in_spec, jax.sharding.PartitionSpec()), check_vma=False,
    )(x)
