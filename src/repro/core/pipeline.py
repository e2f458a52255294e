"""Pipeline-parallel engine (paper C1 + C3 on-mesh): a schedule *compiler*

plus one generalized slot executor, in ``shard_map`` with the ``model`` mesh
axis as the stage axis, streaming microbatch activations stage-to-stage via
``ppermute`` — and, when ``compress=True``, streaming the paper's
*bottleneck codes* (width d_b) instead of full-width activations, cutting
inter-stage bytes by d_model/d_b (64x for the paper's 2048->32).
``wire_codec="int8"`` ships quantized codes on the wire (per-block symmetric
int8, one fp32 scale per block), doubling 64x to the paper's headline 128x.

Faithfulness map:
  miners on one layer-slice   -> devices in one model-axis row
  S3 activation hand-off      -> ppermute along ``model``
  bottleneck block at miner Tx-> encode at stage exit (stage owns W_down)
  post-bottleneck at miner Rx -> decode at stage entry (stage owns W_up of
                                 the previous boundary)
  DP across pipeline replicas -> ``data`` (x ``pod``) axes

Schedules (``PipelineSpec.schedule``; registry ``SCHEDULES``) are all
compiled by ``compile_timetable`` into one ``Timetable``: per-stage,
per-slot role tables over {idle, F, B, W} plus a ring-stash plan (which
ring slot every arriving wire code is written to, and which ring slot every
unit reads).  The timetable is the single source of truth for execution
order, stash lifetime, wire hops, and bubble accounting:

  * ``"gpipe"``  — the golden reference: T = n_micro + n_stages - 1 forward
    ticks; autodiff through the tick scan gives the backward pipeline
    automatically (transpose of ppermute = reverse-direction ppermute), so
    gradients of the wire codes are compressed exactly like activations —
    the paper's symmetrical 128x.  The tick loop's ingest/collect index
    tables are derived from the compiled timetable.  Bubble
    (P-1)/(M+P-1); stash ~ one wire code per tick (checkpointed carry).
  * ``"1f1b"``   — one-forward-one-backward, run by the slot executor.
    Slot maps (equal F/B cost, slot granularity; stage s of P, micro m):
        f(s, m) = s + m              for m <  P - s   (warmup)
        f(s, m) = 2m + s             for m >= P - s   (steady)
        b(s, m) = 2P - 1 - s + 2m
    Same bubble as GPipe but the activation stash shrinks to a
    min(P, M)-slot ring of wire codes.
  * ``"interleaved"`` — Megatron-style virtual stages: each device hosts
    V > 1 *chunks* (chunk c on device c % P, local index c // P), walked
    in groups of P microbatches with a depth-staggered warmup, shrinking
    the bubble to (P-1)/(V*M+P-1).  Needs M % P == 0.  Chunk boundaries
    all carry the wire codec, so interleaved (P, V) is the *same model* as
    gpipe at P*V stages — the loss-parity oracle used by the tests.
  * ``"zerobubble"`` — ZB-H1-style split of backward slots into
    activation-grad ``B`` (sends the upstream cotangent as early as 1F1B
    does) and weight-grad ``W`` (fills former idle slots).  Bubble drops
    to ~1 - 3M/K ≈ 0.11 at P=4/M=8; the W slots re-run the stage forward
    from the stashed code (recompute-from-wire design), and the cotangent
    ring keeps each B's seed alive until its W consumes it.

Boundary codecs: the stage-exit encode (RMSNorm -> W_down -> wire cast) and
stage-entry decode (alpha * (z @ W_up)) run as fused Pallas kernels
(``kernels/bottleneck_fused.py``): one HBM read of the full-width x, one
write of the 64x-smaller code.  Dispatch follows the ``kernels/ops.py``
policy — compiled Pallas on TPU, the identical-math ref.py oracle on other
backends, the kernel bodies under interpret=True when
``REPRO_FORCE_PALLAS_INTERPRET=1`` (how the CPU equivalence suite pins
kernel == oracle).  Under ``wire_codec="int8"`` the slot executor ships and
*stashes* the physical (int8 codes, fp32 scales) pair — the ring holds the
compressed form and dequantizes at consumption (bit-identical to the old
dequantize-then-stash, since q * scale is exact in f32), so the int8 stash
is ~2x smaller than bf16 instead of 2x larger.  The GPipe autodiff carry
must stay a float tensor (an int8 carry would sever the straight-through
gradient channel across the scan transpose), so only the explicit-schedule
rings get the compressed stash.

Used by ``--strategy pipeline`` in launch/train.py + launch/dryrun.py and by
benchmarks/bench_pipeline.py (BENCH_pipeline.json).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels import ops, quant_stream as qs
from repro.models import blocks as blk
from repro.models.layers import (
    dense_init,
    init_embeddings,
    next_token_loss,
    norm_init,
    rmsnorm,
)
from repro.models.layers import embed as embed_fn
from repro.models.layers import logits as logits_fn


SCHEDULES = ("gpipe", "1f1b", "interleaved", "zerobubble", "decode")
WIRE_CODECS = ("none", "int8")

# Timetable roles: every (stage, slot) cell does exactly one of these.
ROLE_IDLE, ROLE_F, ROLE_B, ROLE_W = 0, 1, 2, 3
ROLE_NAMES = ("idle", "F", "B", "W")

_NEVER = 1 << 30


class ScheduleError(ValueError):
    """A (schedule, P, M, V) combination the compiler rejects."""


@dataclasses.dataclass(frozen=True)
class PipelineSpec:
    n_stages: int
    n_microbatches: int
    compress: bool = True            # stream bottleneck codes, not residuals
    bottleneck_dim: int = 32
    wire_dtype: Any = jnp.bfloat16
    schedule: str = "gpipe"          # one of SCHEDULES (compiler registry)
    wire_codec: str = "none"         # "none" | "int8" (quantized codes)
    fuse_boundary: bool = True       # fused Pallas boundary encode/decode
    virtual_stages: int = 1          # chunks per device (interleaved only)

    def __post_init__(self):
        assert self.wire_codec in WIRE_CODECS, self.wire_codec
        assert self.wire_codec == "none" or self.compress, \
            "int8 wire codec quantizes bottleneck codes; needs compress=True"
        # one compile validates schedule name, V, and M % P constraints
        # (lru-cached, so every later timetable() call is free)
        compile_timetable(self.schedule, self.n_stages, self.n_microbatches,
                          self.virtual_stages)

    @property
    def n_chunks(self) -> int:
        """Model chunks = codec boundaries + 1: P * V."""
        return self.n_stages * self.virtual_stages

    def timetable(self) -> "Timetable":
        return compile_timetable(self.schedule, self.n_stages,
                                 self.n_microbatches, self.virtual_stages)

    def wire_width(self, cfg: ModelConfig) -> int:
        return self.bottleneck_dim if self.compress else cfg.d_model

    def carry_dtype(self):
        """On-device dtype of a *decoded* wire code.  int8 codes dequantize
        to exact f32 products (q * scale), so decoded carries hold f32; the
        explicit-schedule rings stash the (int8, scales) pair instead
        (``schedule_stats``/``wire_bytes_per_hop`` account both honestly)."""
        return jnp.float32 if self.wire_codec == "int8" else self.wire_dtype


# ---------------------------------------------------------------------------
# Schedule compiler: (schedule, P, M, V) -> Timetable
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class Timetable:
    """Compiled slot program for one pipeline schedule.

    All per-slot tables are (P, K) int32, indexed [stage, slot].  ``role``
    says what the stage does that slot; ``micro``/``vstage`` which
    (microbatch, local chunk) the unit works on (0 when idle).  The ring
    plan: ``z_arrive[d, t]`` is the forward-ring slot an arriving wire code
    is written to at slot t (-1: no arrival); ``z_src[d, t]`` the ring slot
    this slot's unit reads its input code from.  ``g_arrive``/``g_src``
    are the same for the backward (cotangent) ring — for ``zerobubble`` a
    cotangent stays live from its B until its W consumes it.

    ``f_slot``/``b_slot``/``w_slot`` are the raw (C, M) slot maps (w_slot
    is -1 outside zerobubble) kept for tests and accounting.
    """
    schedule: str
    n_stages: int
    n_virtual: int
    n_micro: int
    n_slots: int
    role: np.ndarray
    micro: np.ndarray
    vstage: np.ndarray
    z_ring: int
    g_ring: int
    z_arrive: np.ndarray
    z_src: np.ndarray
    g_arrive: np.ndarray
    g_src: np.ndarray
    f_slot: np.ndarray
    b_slot: np.ndarray
    w_slot: np.ndarray

    @property
    def n_chunks(self) -> int:
        return self.n_stages * self.n_virtual

    def work_units(self) -> int:
        return int((self.role != ROLE_IDLE).sum())

    def bubble_fraction(self) -> float:
        """Measured idle fraction of the executed timetable (not a closed
        form): 1 - work cells / (P * K)."""
        return 1.0 - self.work_units() / (self.n_stages * self.n_slots)


def _interleaved_slots(Pn: int, M: int, V: int):
    """Megatron-order virtual-stage schedule: per device, M//P groups of P
    microbatches walk the V chunks (forward: shallow->deep, backward:
    deep->shallow) with a depth-staggered warmup of (V-1)*P + (P-d)
    forwards, then strict B/F alternation; units dispatch in list order as
    soon as their producer's hand-off (one-slot transit) has arrived.
    Hits the ideal K = 2(VM + P - 1), i.e. bubble (P-1)/(VM+P-1)."""
    C = Pn * V
    orders = []
    for d in range(Pn):
        fseq = [("F", v * Pn + d, g * Pn + i)
                for g in range(M // Pn) for v in range(V) for i in range(Pn)]
        bseq = [("B", v * Pn + d, g * Pn + i)
                for g in range(M // Pn) for v in reversed(range(V))
                for i in range(Pn)]
        warm = min((V - 1) * Pn + (Pn - d), len(fseq))
        order = list(fseq[:warm])
        fi, bi = warm, 0
        while fi < len(fseq) or bi < len(bseq):
            if bi < len(bseq):
                order.append(bseq[bi])
                bi += 1
            if fi < len(fseq):
                order.append(fseq[fi])
                fi += 1
        orders.append(order)

    f: dict = {}
    b: dict = {}
    ptr = [0] * Pn
    t = 0
    while any(ptr[d] < len(orders[d]) for d in range(Pn)):
        for d in range(Pn):
            if ptr[d] >= len(orders[d]):
                continue
            kind, c, m = orders[d][ptr[d]]
            if kind == "F":
                ready = c == 0 or f.get((c - 1, m), _NEVER) + 1 <= t
            elif c == C - 1:
                ready = f.get((c, m), _NEVER) + 1 <= t
            else:
                ready = ((c, m) in f
                         and b.get((c + 1, m), _NEVER) + 1 <= t)
            if ready:
                (f if kind == "F" else b)[(c, m)] = t
                ptr[d] += 1
        t += 1
        if t > 4 * (V * M + Pn) + 8:
            raise ScheduleError(
                f"interleaved dispatch deadlocked at P={Pn} M={M} V={V}")
    return f, b, max(b.values()) + 1


def _slot_maps(schedule: str, Pn: int, M: int, V: int):
    """(f, b, w) slot dicts keyed (chunk, micro) plus loop length K."""
    f: dict = {}
    b: dict = {}
    w: dict = {}
    if schedule == "gpipe":
        Kf = M + Pn - 1
        for s in range(Pn):
            for m in range(M):
                f[(s, m)] = s + m
                b[(s, m)] = Kf + (Pn - 1 - s) + m
        K = 2 * Kf
    elif schedule == "decode":
        # Forward-only token round: micro-batch slots are request lanes,
        # each lane advances one token per round.  Lane m enters stage s
        # at slot s + m; there is no backward/weight pass, so the round
        # closes after the last lane drains the last stage.
        for s in range(Pn):
            for m in range(M):
                f[(s, m)] = s + m
        K = M + Pn - 1
    elif schedule in ("1f1b", "zerobubble"):
        for s in range(Pn):
            for m in range(M):
                f[(s, m)] = s + m if m < Pn - s else 2 * m + s
                b[(s, m)] = 2 * Pn - 1 - s + 2 * m
        K = 2 * (M + Pn - 1)
        if schedule == "zerobubble":
            # W(s, m) fills the first idle slot after its own B(s, m) —
            # in-order per stage, so the cotangent ring frees FIFO
            for s in range(Pn):
                used = ({f[(s, m)] for m in range(M)}
                        | {b[(s, m)] for m in range(M)})
                t = 0
                for m in range(M):
                    t = max(t, b[(s, m)] + 1)
                    while t in used:
                        t += 1
                    w[(s, m)] = t
                    used.add(t)
            K = max(K, max(w.values()) + 1)
    else:
        f, b, K = _interleaved_slots(Pn, M, V)
    return f, b, w, K


def _greedy_ring(entries: dict):
    """First-free interval allocation: {key: (arrive, last_use)} ->
    ({key: ring_slot}, capacity).  A ring slot frees the slot after its
    entry's last consumer."""
    free_at: list = []
    assign: dict = {}
    for key, (arrive, last) in sorted(entries.items(),
                                      key=lambda kv: (kv[1][0], kv[0])):
        for i, fa in enumerate(free_at):
            if fa <= arrive:
                assign[key] = i
                free_at[i] = last + 1
                break
        else:
            assign[key] = len(free_at)
            free_at.append(last + 1)
    return assign, max(1, len(free_at))


def _check_timetable(tt: "Timetable"):
    """Self-check: one unit per cell, F < B < W per (chunk, micro) with
    one-slot transit between neighbours, every send matched by a receive,
    and ring lifetimes within the declared capacities."""
    Pn, V, M, K = tt.n_stages, tt.n_virtual, tt.n_micro, tt.n_slots
    C = Pn * V
    # forward-only timetables (the decode schedule) have no B/W cells:
    # skip the backward-ordering/transit checks and expect zero B/W roles
    fwd_only = bool((tt.b_slot < 0).all())
    for c in range(C):
        d = c % Pn
        for m in range(M):
            fs, bs = int(tt.f_slot[c, m]), int(tt.b_slot[c, m])
            if fwd_only:
                if not 0 <= fs < K:
                    raise ScheduleError(
                        f"F slot out of range: chunk {c} micro {m}")
            elif not 0 <= fs < bs < K:
                raise ScheduleError(f"F/B order broken: chunk {c} micro {m}")
            if c > 0 and fs < int(tt.f_slot[c - 1, m]) + 1:
                raise ScheduleError(f"F transit broken: chunk {c} micro {m}")
            if not fwd_only and c < C - 1 \
                    and bs < int(tt.b_slot[c + 1, m]) + 1:
                raise ScheduleError(f"B transit broken: chunk {c} micro {m}")
            ws = int(tt.w_slot[c, m])
            if ws >= 0 and not bs < ws < K:
                raise ScheduleError(f"W order broken: chunk {c} micro {m}")
            if c > 0:
                # the code sent at f(c-1, m) must be received into the ring
                # one slot later on this chunk's device
                if int(tt.z_arrive[d, int(tt.f_slot[c - 1, m]) + 1]) < 0:
                    raise ScheduleError(
                        f"unmatched F send: chunk {c - 1} micro {m}")
            if not fwd_only and c < C - 1:
                if int(tt.g_arrive[d, int(tt.b_slot[c + 1, m]) + 1]) < 0:
                    raise ScheduleError(
                        f"unmatched B send: chunk {c + 1} micro {m}")
    counts = [(tt.role == r).sum() for r in (ROLE_F, ROLE_B, ROLE_W)]
    expect_b = 0 if fwd_only else C * M
    expect_w = C * M if (tt.w_slot >= 0).any() else 0
    if counts[0] != C * M or counts[1] != expect_b or counts[2] != expect_w:
        raise ScheduleError(f"role counts off: {counts}")
    if (tt.z_arrive >= tt.z_ring).any() or (tt.z_src >= tt.z_ring).any():
        raise ScheduleError("z ring index out of capacity")
    if (tt.g_arrive >= tt.g_ring).any() or (tt.g_src >= tt.g_ring).any():
        raise ScheduleError("g ring index out of capacity")


@functools.lru_cache(maxsize=None)
def compile_timetable(schedule: str, n_stages: int, n_micro: int,
                      n_virtual: int = 1) -> Timetable:
    """Compile + validate the slot program for one schedule point."""
    if schedule not in SCHEDULES:
        raise ScheduleError(
            f"unknown schedule {schedule!r}; registry: {SCHEDULES}")
    Pn, M, V = int(n_stages), int(n_micro), int(n_virtual)
    if Pn < 1 or M < 1:
        raise ScheduleError(f"need n_stages, n_micro >= 1: {Pn}, {M}")
    if schedule == "interleaved":
        if V < 2:
            raise ScheduleError(
                "interleaved needs virtual_stages >= 2 (V=1 is exactly "
                "1f1b; use that)")
        if Pn < 2:
            raise ScheduleError("interleaved needs n_stages >= 2")
        if M % Pn != 0:
            raise ScheduleError(
                f"interleaved walks microbatches in groups of P: need "
                f"n_microbatches % n_stages == 0, got {M} % {Pn}")
    elif V != 1:
        raise ScheduleError(
            f"{schedule} runs one chunk per device (virtual_stages=1)")

    C = Pn * V
    f, b, w, K = _slot_maps(schedule, Pn, M, V)

    role = np.zeros((Pn, K), np.int32)
    micro = np.zeros((Pn, K), np.int32)
    vstage = np.zeros((Pn, K), np.int32)
    for tbl, r in ((f, ROLE_F), (b, ROLE_B), (w, ROLE_W)):
        for (c, m), t in tbl.items():
            d = c % Pn
            if role[d, t] != ROLE_IDLE:
                raise ScheduleError(
                    f"slot conflict: stage {d} slot {t} "
                    f"({ROLE_NAMES[role[d, t]]} vs {ROLE_NAMES[r]})")
            role[d, t] = r
            micro[d, t] = m
            vstage[d, t] = c // Pn

    # ring plans: a stashed input code lives arrival -> last recompute
    # (W if the schedule splits backward, else B); a cotangent lives
    # arrival -> its consumer (B, and W for zerobubble)
    def last_use(c, m):
        if w:
            return w[(c, m)]
        if b:
            return b[(c, m)]
        return f[(c, m)]       # forward-only: consumed at its own F slot

    z_assign: dict = {}
    g_assign: dict = {}
    z_cap = g_cap = 1
    for d in range(Pn):
        z_entries = {(c, m): (f[(c - 1, m)] + 1, last_use(c, m))
                     for c in range(C) for m in range(M)
                     if c % Pn == d and c > 0}
        g_entries = {(c, m): (b[(c + 1, m)] + 1, last_use(c, m))
                     for c in range(C) for m in range(M)
                     if c % Pn == d and c < C - 1} if b else {}
        za, zc = _greedy_ring(z_entries)
        ga, gc = _greedy_ring(g_entries)
        z_assign.update(za)
        g_assign.update(ga)
        z_cap, g_cap = max(z_cap, zc), max(g_cap, gc)

    z_arrive = np.full((Pn, K), -1, np.int32)
    z_src = np.zeros((Pn, K), np.int32)
    g_arrive = np.full((Pn, K), -1, np.int32)
    g_src = np.zeros((Pn, K), np.int32)
    for (c, m), ring_i in z_assign.items():
        d = c % Pn
        z_arrive[d, f[(c - 1, m)] + 1] = ring_i
        z_src[d, f[(c, m)]] = ring_i
        if b:
            z_src[d, b[(c, m)]] = ring_i
        if w:
            z_src[d, w[(c, m)]] = ring_i
    for (c, m), ring_i in g_assign.items():
        d = c % Pn
        g_arrive[d, b[(c + 1, m)] + 1] = ring_i
        g_src[d, b[(c, m)]] = ring_i
        if w:
            g_src[d, w[(c, m)]] = ring_i

    def slot_arr(tbl):
        out = np.full((C, M), -1, np.int32)
        for (c, m), t in tbl.items():
            out[c, m] = t
        return out

    tt = Timetable(
        schedule=schedule, n_stages=Pn, n_virtual=V, n_micro=M, n_slots=K,
        role=role, micro=micro, vstage=vstage,
        z_ring=z_cap, g_ring=g_cap,
        z_arrive=z_arrive, z_src=z_src, g_arrive=g_arrive, g_src=g_src,
        f_slot=slot_arr(f), b_slot=slot_arr(b), w_slot=slot_arr(w))
    _check_timetable(tt)
    return tt


def _gpipe_io_tables(n_stages: int, n_micro: int):
    """The GPipe tick loop's ingest/collect indices, re-derived from the
    compiled timetable (bit-identical to the old clip arithmetic): per
    forward tick t, (microbatch stage 0 ingests, collector index on the
    last stage, collector-write flag)."""
    tt = compile_timetable("gpipe", n_stages, n_micro)
    T = n_micro + n_stages - 1
    in_m = np.zeros(T, np.int32)
    out_m = np.zeros(T, np.int32)
    out_ok = np.zeros(T, bool)
    cur = 0
    for t in range(T):
        if tt.role[0, t] == ROLE_F:
            cur = int(tt.micro[0, t])
        in_m[t] = cur
    cur = 0
    for t in range(T):
        if tt.role[-1, t] == ROLE_F:
            cur = int(tt.micro[-1, t])
            out_ok[t] = True
        out_m[t] = cur
    return in_m, out_m, out_ok


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def init_pipeline_params(key, cfg: ModelConfig, spec: PipelineSpec) -> dict:
    """Stage-stacked layout: every leading axis ``n_stages`` shards over

    ``model``; with ``virtual_stages=V > 1`` a second axis V follows it
    (position [d, v] holds chunk c = v*P + d).  Chunk c owns: its block
    slice, W_down of boundary c (encode at exit; unused on the last chunk)
    and W_up of boundary c-1 (decode at entry; unused on chunk 0).  RNG
    folds by *global chunk index*, so interleaved (P, V) params equal
    gpipe params at P*V stages chunk-for-chunk — the loss-parity oracle."""
    kinds = blk.period_kinds(cfg)
    assert kinds in (["attn_dense"], ["attn_moe"]), (
        "pipeline strategy supports uniform decoder stacks; "
        f"{cfg.arch_id} period={kinds}")
    kind = kinds[0]
    Pn, V, C = spec.n_stages, spec.virtual_stages, spec.n_chunks
    assert cfg.n_layers % C == 0, (cfg.n_layers, C)
    l_per = cfg.n_layers // C

    ks = jax.random.split(key, 4)

    def chunk_blocks(c):
        layers = [blk.init_block(jax.random.fold_in(ks[0], c * 1000 + l),
                                 kind, cfg) for l in range(l_per)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)

    def stack_chunks(make):
        """(P, ...) for V == 1 (seed-exact layout), else (P, V, ...)."""
        if V == 1:
            return jax.tree.map(lambda *xs: jnp.stack(xs),
                                *[make(c) for c in range(C)])
        rows = [jax.tree.map(lambda *xs: jnp.stack(xs),
                             *[make(v * Pn + d) for v in range(V)])
                for d in range(Pn)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *rows)

    d, db = cfg.d_model, spec.bottleneck_dim
    params = {
        "embeds": init_embeddings(ks[1], cfg),
        "final_norm": norm_init(cfg.d_model),
        "stages": {"blocks": stack_chunks(chunk_blocks)},
    }
    if spec.compress:
        params["stages"]["enc_norm"] = stack_chunks(
            lambda c: jnp.ones((d,), jnp.float32))
        params["stages"]["w_down"] = stack_chunks(
            lambda c: dense_init(jax.random.fold_in(ks[2], c), d, db))
        params["stages"]["w_up_prev"] = stack_chunks(
            lambda c: dense_init(jax.random.fold_in(ks[3], c), db, d,
                                 scale=1.0 / np.sqrt(db)))
        params["stages"]["alpha_dec"] = stack_chunks(
            lambda c: jnp.asarray(0.5, jnp.float32))
    return params


def pipeline_param_shardings(params, mesh) -> dict:
    """Mesh layout of an ``init_pipeline_params`` tree (arrays or shapes):
    every ``stages`` leaf splits its leading stage axis over ``model`` —
    the layout the step's shard_map consumes — and the embeddings and
    final norm are replicated."""
    from jax.sharding import NamedSharding
    stage, rep = NamedSharding(mesh, P("model")), NamedSharding(mesh, P())
    out = jax.tree.map(lambda _: rep, params)
    out["stages"] = jax.tree.map(lambda _: stage, params["stages"])
    return out


# ---------------------------------------------------------------------------
# Boundary codecs (fused Pallas hot path, jnp fallback kept as oracle path)
# ---------------------------------------------------------------------------


def _encode_boundary(x, stages, cfg: ModelConfig, spec: PipelineSpec,
                     codec: bool = True):
    """Stage exit: RMSNorm -> W_down -> wire cast, one fused kernel (one HBM
    read of full-width x, one write of the d_model/d_b-smaller code); then
    the optional differentiable int8 wire roundtrip.  Kernel dispatch
    follows the ops.py policy: compiled Pallas on TPU, the identical-math
    oracle elsewhere (REPRO_FORCE_PALLAS_INTERPRET=1 forces the kernel
    bodies under interpret, as the equivalence suite does)."""
    if not spec.compress:
        z = x.astype(spec.wire_dtype)
    elif spec.fuse_boundary:
        z = ops.bottleneck_encode(x, stages["enc_norm"], stages["w_down"],
                                  eps=cfg.norm_eps,
                                  wire_dtype=spec.carry_dtype())
    else:
        xn = rmsnorm(x, stages["enc_norm"], cfg.norm_eps)
        z = (xn.astype(jnp.float32) @ stages["w_down"].astype(jnp.float32)
             ).astype(spec.carry_dtype())
    if codec and spec.wire_codec == "int8":
        z = ops.int8_wire_roundtrip(z)
    return z


def _decode_boundary(z, stages, spec: PipelineSpec, compute_dtype):
    """Stage entry: alpha * (z @ W_up) — fused gated decode (one full-width
    write instead of matmul write + scale pass)."""
    if not spec.compress:
        return z.astype(compute_dtype)
    if spec.fuse_boundary:
        return ops.bottleneck_decode_gated(z, stages["w_up_prev"],
                                           stages["alpha_dec"],
                                           out_dtype=compute_dtype)
    r = (z.astype(jnp.float32) @ stages["w_up_prev"].astype(jnp.float32)
         ).astype(compute_dtype)
    return stages["alpha_dec"].astype(compute_dtype) * r


def _traced_zero(x) -> jax.Array:
    """A scalar f32 zero derived from a traced array.  Rank-0 *constants*
    inside a shard_map body break its transpose on jax<=0.4.x (the const is
    promoted to a body output whose P() spec fails _check_names), so scan
    carries must originate from traced values."""
    return x.ravel()[0].astype(jnp.float32) * 0.0


# ---------------------------------------------------------------------------
# The pipelined forward (GPipe)
# ---------------------------------------------------------------------------


def _stage_forward(stage_params, x, cfg: ModelConfig, kind: str,
                   positions, remat: bool):
    """Apply this stage's block slice (inner scan over layers)."""
    ctx = blk.BlockCtx(cfg=cfg, ma=None, positions=positions)

    def body(h, layer_params):
        h, _, _ = blk.apply_block(kind, layer_params, h, ctx, None)
        return h, None

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, stage_params)
    return x


def pipeline_apply(params, x_micro, cfg: ModelConfig, spec: PipelineSpec,
                   mesh, batch_axes: tuple[str, ...] = ("data",),
                   remat: bool = True):
    """x_micro: (n_micro, B, S, d_model) embedded microbatches (B = global

    batch / n_micro).  Returns (n_micro, B, S, d_model) block-stack outputs.
    GPipe-structured forward sweep (virtual_stages == 1 layouts only).
    """
    assert spec.virtual_stages == 1, \
        "pipeline_apply is the V=1 forward; interleaved runs the executor"
    kind = blk.period_kinds(cfg)[0]
    n_stages, n_micro = spec.n_stages, spec.n_microbatches
    d_wire = spec.wire_width(cfg)
    S = x_micro.shape[2]
    positions = jnp.arange(S, dtype=jnp.int32)[None]
    in_m, out_m, out_ok = _gpipe_io_tables(n_stages, n_micro)
    in_tbl, out_tbl = jnp.asarray(in_m), jnp.asarray(out_m)
    ok_tbl = jnp.asarray(out_ok)

    def body(x_all, stages):
        # local views: x_all (n_micro, B_loc, S, D); stages leading dim == 1
        stages = jax.tree.map(lambda a: a[0], stages)
        B_loc = x_all.shape[1]
        stage = jax.lax.axis_index("model")
        pos = jnp.broadcast_to(positions, (B_loc, S))
        compute_dtype = x_all.dtype

        z0 = jnp.zeros((B_loc, S, d_wire), spec.carry_dtype())
        out0 = jnp.zeros_like(x_all)

        def tick(carry, t):
            z, outputs = carry
            # ---- stage entry: ingest (stage 0) or decode the wire code ----
            x_in = jax.lax.dynamic_index_in_dim(
                x_all, in_tbl[t], 0, keepdims=False)
            r = _decode_boundary(z, stages, spec, compute_dtype)
            x = jnp.where(stage == 0, x_in, r)
            # ---- stage compute ----
            x = _stage_forward(stages["blocks"], x, cfg, kind, pos, remat)
            # ---- stage exit: encode the wire code ----
            z_out = _encode_boundary(x, stages, cfg, spec)
            # ---- collect finished microbatches on the last stage ----
            out_idx = out_tbl[t]
            is_out = (stage == n_stages - 1) & ok_tbl[t]
            cur = jax.lax.dynamic_index_in_dim(outputs, out_idx, 0,
                                               keepdims=False)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(is_out, x, cur), out_idx, 0)
            # ---- stream to the next stage (no wraparound: stage0 gets 0) ----
            z_next = jax.lax.ppermute(
                z_out, "model", [(i, i + 1) for i in range(n_stages - 1)])
            return (z_next, outputs), None

        T = n_micro + n_stages - 1
        (z, outputs), _ = jax.lax.scan(tick, (z0, out0),
                                       jnp.arange(T, dtype=jnp.int32))
        # only the last stage holds real outputs; psum replicates them
        outputs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outputs, jnp.zeros_like(outputs)),
            "model")
        return outputs

    stage_specs = jax.tree.map(lambda _: P("model"), params["stages"])
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, batch_axes, None, None), stage_specs),
        out_specs=P(None, batch_axes, None, None), check_vma=False,
    )(x_micro, params["stages"])


# ---------------------------------------------------------------------------
# End-to-end pipelined train/loss step
# ---------------------------------------------------------------------------


def pipeline_loss(params, batch, cfg: ModelConfig, spec: PipelineSpec, mesh,
                  batch_axes: tuple[str, ...] = ("data",), z_loss: float = 1e-4,
                  compute_dtype=jnp.bfloat16):
    if spec.virtual_stages > 1:
        # interleaved layouts only exist for the slot executor
        loss, _ = pipeline_timetable_grads(params, batch, cfg, spec, mesh,
                                           batch_axes, z_loss, compute_dtype)
        return loss
    tokens, labels = batch["tokens"], batch["labels"]
    B, S = tokens.shape
    n_micro = spec.n_microbatches
    assert B % n_micro == 0, (B, n_micro)
    x = embed_fn(params["embeds"], tokens, cfg, None, compute_dtype)
    x = x.reshape(n_micro, B // n_micro, S, -1)
    y = pipeline_apply(params, x, cfg, spec, mesh, batch_axes)
    # loss head is MICROBATCHED (scan + remat): a full-batch fp32 logits
    # tensor would be (B, S, V/16) ≈ 34 GB/device (§Perf cell C iteration 4:
    # 145 GiB/device -> fits, and the logits all-gather drops with it)
    labels_m = labels.reshape(n_micro, B // n_micro, S)

    def head(y_mb, lab_mb):
        h = rmsnorm(y_mb, params["final_norm"], cfg.norm_eps)
        lgts = logits_fn(params["embeds"], h, cfg, None)
        return next_token_loss(lgts, lab_mb, z_loss)

    head = jax.checkpoint(head, policy=jax.checkpoint_policies.nothing_saveable)

    def body(acc, xs):
        y_mb, lab_mb = xs
        return acc + head(y_mb, lab_mb), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (y, labels_m))
    return total / n_micro


def wire_bytes_per_hop(cfg: ModelConfig, spec: PipelineSpec,
                       global_batch: int, seq: int,
                       data_shards: int = 1) -> int:
    """On-wire bytes for one full microbatch sweep across one boundary.

    For the int8 codec this accounts the fp32 scales honestly: one per
    quantization block of the per-device per-microbatch code tensor — the
    block the runtime codec actually quantizes (``data_shards`` matters:
    a sharded microbatch can fall back to per-row scales)."""
    width = spec.wire_width(cfg)
    n = global_batch * seq * width
    if spec.wire_codec == "int8":
        micro_elems = (max(global_batch // spec.n_microbatches // data_shards,
                           1) * seq * width)
        block = qs.wire_block(micro_elems, width)
        return n + (n // block) * 4
    return n * jnp.dtype(spec.wire_dtype).itemsize


def schedule_stats(cfg: ModelConfig, spec: PipelineSpec, global_batch: int,
                   seq: int, data_shards: int = 1) -> dict:
    """Schedule accounting derived from the compiled timetable and the real
    carry structures:

    * ``bubble_fraction``   — idle fraction of the *executed timetable*
      (``Timetable.bubble_fraction``, not a closed form; equals
      (P-1)/(M+P-1) for gpipe/1f1b — the tests pin that identity)
    * ``stash_codes/bytes`` — per-device activation stash: GPipe saves the
      checkpointed tick carry's wire code once per tick (T float codes —
      an int8 carry would sever the straight-through gradient, so the
      autodiff path cannot stash pairs); explicit schedules allocate the
      compiler's z-ring, which under int8 stashes the physical
      (codes, scales) pair
    * ``grad_ring_codes``   — cotangent-ring slots (zerobubble keeps each
      B's seed alive until its W)
    * ``carry_code_bytes``  — one decoded in-flight code (B_loc, S, d_wire)
    * ``wire_bytes_per_hop``— on-wire bytes per boundary per sweep
    """
    Pn, M = spec.n_stages, spec.n_microbatches
    tt = spec.timetable()
    width = spec.wire_width(cfg)
    B_loc = max(global_batch // M // data_shards, 1)
    code_bytes = (B_loc * seq * width
                  * jnp.dtype(spec.carry_dtype()).itemsize)
    if spec.wire_codec == "int8":
        ring_code_bytes = qs.wire_nbytes((B_loc, seq, width))
    else:
        ring_code_bytes = code_bytes
    ticks = M + Pn - 1
    if spec.schedule == "gpipe":
        loop_len = ticks
        stash_codes = ticks
        stash_bytes = ticks * code_bytes
        grad_ring = 0
    else:
        loop_len = tt.n_slots
        stash_codes = tt.z_ring
        stash_bytes = tt.z_ring * ring_code_bytes
        grad_ring = tt.g_ring
    return {
        "schedule": spec.schedule,
        "n_stages": Pn,
        "n_microbatches": M,
        "virtual_stages": spec.virtual_stages,
        "loop_length": loop_len,
        "timetable_slots": tt.n_slots,
        "bubble_fraction": tt.bubble_fraction(),
        "carry_code_bytes": int(code_bytes),
        "ring_code_bytes": int(ring_code_bytes),
        "stash_codes": int(stash_codes),
        "stash_bytes": int(stash_bytes),
        "grad_ring_codes": int(grad_ring),
        "wire_bytes_per_hop": int(
            wire_bytes_per_hop(cfg, spec, global_batch, seq,
                               data_shards=data_shards)),
    }


# ---------------------------------------------------------------------------
# Fused pipeline: embed on stage 0, loss on the last stage (paper §2.2:
# 'Miners in the first layer also handle data ingestion and tokenization,
# while those in the final layer compute the training loss.')
# ---------------------------------------------------------------------------


def pipeline_loss_fused(params, batch, cfg: ModelConfig, spec: PipelineSpec,
                        mesh, batch_axes: tuple[str, ...] = ("data",),
                        z_loss: float = 1e-4, compute_dtype=jnp.bfloat16):
    """One shard_map for the whole step: tokens (tiny) replicate to stages

    instead of embedded activations; the loss is computed on the last stage
    and psum'd as a scalar.  §Perf cell C iteration 5: removes the
    537 MB x 2 x ticks GSPMD resharding permutes and the 4.5 GB output
    all-reduce of the v1 layout — inter-stage traffic is then just the
    (compressed) wire codes, i.e. the paper's §4 claim made visible on-mesh.
    The tick loop's ingest/collect indices come from the compiled gpipe
    timetable (``_gpipe_io_tables``).
    """
    kind = blk.period_kinds(cfg)[0]
    tokens, labels = batch["tokens"], batch["labels"]
    B, S = tokens.shape
    n_stages, n_micro = spec.n_stages, spec.n_microbatches
    assert B % n_micro == 0
    assert spec.virtual_stages == 1, \
        "the fused autodiff loop is the V=1 golden path"
    d_wire = spec.wire_width(cfg)
    Bm = B // n_micro
    tokens_m = tokens.reshape(n_micro, Bm, S)
    labels_m = labels.reshape(n_micro, Bm, S)
    positions = jnp.arange(S, dtype=jnp.int32)[None]
    in_m, out_m, out_ok = _gpipe_io_tables(n_stages, n_micro)
    in_tbl, out_tbl = jnp.asarray(in_m), jnp.asarray(out_m)
    ok_tbl = jnp.asarray(out_ok)

    def body(toks, labs, embed_tbl, unembed_tbl, final_gamma, stages):
        stages = jax.tree.map(lambda a: a[0], stages)
        B_loc = toks.shape[1]
        stage = jax.lax.axis_index("model")
        pos = jnp.broadcast_to(positions, (B_loc, S))
        last = n_stages - 1

        z0 = jnp.zeros((B_loc, S, d_wire), spec.carry_dtype())
        out0 = jnp.zeros((n_micro, B_loc, S, cfg.d_model), compute_dtype)

        # §Perf cell C iteration 7 (winner of 6/7/8 — see EXPERIMENTS.md):
        # the tick body is checkpointed, so the backward pipeline re-derives
        # each tick from its carry, whose activation part is the COMPRESSED
        # wire code z — the paper's 64x compression also shrinks the GPipe
        # activation stash.  The in-carry output collector is donated/
        # aliased in place by XLA (the ys-collection variants measured
        # strictly worse).
        def tick(carry, t):
            z, outputs = carry
            t_in = jax.lax.dynamic_index_in_dim(
                toks, in_tbl[t], 0, keepdims=False)
            # stage 0 ingests tokens (paper: first-layer miners tokenize);
            # the embedding gather is tiny next to a full-width activation
            x_in = jnp.take(embed_tbl, t_in, axis=0).astype(compute_dtype)
            r = _decode_boundary(z, stages, spec, compute_dtype)
            x = jnp.where(stage == 0, x_in, r)
            x = _stage_forward(stages["blocks"], x, cfg, kind, pos, True)
            z_out = _encode_boundary(x, stages, cfg, spec)
            out_idx = out_tbl[t]
            is_out = (stage == last) & ok_tbl[t]
            cur = jax.lax.dynamic_index_in_dim(outputs, out_idx, 0,
                                               keepdims=False)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(is_out, x, cur), out_idx, 0)
            z_next = jax.lax.ppermute(
                z_out, "model", [(i, i + 1) for i in range(n_stages - 1)])
            return (z_next, outputs), None

        tick = jax.checkpoint(tick,
                              policy=jax.checkpoint_policies.nothing_saveable)
        T = n_micro + n_stages - 1
        (_, outputs), _ = jax.lax.scan(tick, (z0, out0),
                                       jnp.arange(T, dtype=jnp.int32))

        # ---- loss head on the last stage, microbatched + remat ----
        pad_mask = (jnp.arange(unembed_tbl.shape[0]) >= cfg.vocab_size
                    ) * (-1e9)

        def head(y_mb, lab_mb):
            h = rmsnorm(y_mb, final_gamma, cfg.norm_eps)
            lgts = jnp.einsum("bsd,vd->bsv", h.astype(jnp.float32),
                              unembed_tbl.astype(jnp.float32)) + pad_mask
            return next_token_loss(lgts, lab_mb, z_loss)

        head = jax.checkpoint(head,
                              policy=jax.checkpoint_policies.nothing_saveable)

        def loss_body(acc, xs):
            y_mb, lab_mb = xs
            return acc + head(y_mb, lab_mb), None

        local_loss, _ = jax.lax.scan(loss_body, _traced_zero(outputs),
                                     (outputs, labs))
        loss = jax.lax.psum(
            jnp.where(stage == last, local_loss, 0.0), "model") / n_micro
        return jax.lax.pmean(loss, batch_axes)

    stage_specs = jax.tree.map(lambda _: P("model"), params["stages"])
    unembed = params["embeds"].get("unembed", params["embeds"]["embed"])
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, batch_axes, None), P(None, batch_axes, None),
                  P(None, None), P(None, None), P(None), stage_specs),
        out_specs=P(), check_vma=False,
    )(tokens_m, labels_m, params["embeds"]["embed"], unembed,
      params["final_norm"], params["stages"])


# ---------------------------------------------------------------------------
# Generalized slot executor: runs any compiled explicit-backward timetable
# (1f1b / interleaved / zerobubble) — loss AND grads in one shard_map
# ---------------------------------------------------------------------------


def pipeline_timetable_grads(params, batch, cfg: ModelConfig,
                             spec: PipelineSpec, mesh,
                             batch_axes: tuple[str, ...] = ("data",),
                             z_loss: float = 1e-4,
                             compute_dtype=jnp.bfloat16):
    """One shard_map computing ``(loss, grads)`` by replaying the compiled

    ``Timetable``.  Each slot dispatches its table role via ``lax.switch``
    — idle, F, B, or (zerobubble) W — so a stage only pays for the work its
    slot actually does: F slots run the primal blocks alone (no loss head,
    no pullback); B slots re-run the chunk's forward from the stashed
    *wire code* under ``jax.vjp`` (decode -> blocks -> encode + loss head),
    seed the cotangent from the cotangent ring (or 1.0 for the final
    chunk's loss), and — for 1f1b/interleaved — accumulate param grads in
    the same pullback; zerobubble's B pulls back to the activation only
    (the upstream hand-off leaves as early as 1F1B's) while its W re-runs
    the same vjp restricted to params in a former idle slot, consuming the
    cotangent the ring kept alive.  ``lax.switch`` on the per-device role
    is legal under shard_map here because the branches contain no
    collectives — the two ``ppermute`` hand-offs stay outside, executed by
    every device each slot.  Ring writes/reads use the compiler's
    ring-stash plan verbatim; under ``wire_codec="int8"`` the rings and
    hand-offs carry the physical (int8 codes, fp32 scales) pair and
    dequantize at consumption — bit-identical values to the old
    dequantize-then-stash (q * scale is exact in f32), at ~half the bf16
    ring bytes.

    Returns grads matching ``jax.grad(pipeline_loss_fused)``: per-stage
    params stay per-stage, shared params (embeddings, final norm) are
    psum'd over stages and pmean'd over the batch axes.
    """
    kind = blk.period_kinds(cfg)[0]
    tokens, labels = batch["tokens"], batch["labels"]
    B, S = tokens.shape
    Pn, M, V = spec.n_stages, spec.n_microbatches, spec.virtual_stages
    assert B % M == 0
    d_wire = spec.wire_width(cfg)
    Bm = B // M
    tokens_m = tokens.reshape(M, Bm, S)
    labels_m = labels.reshape(M, Bm, S)
    positions = jnp.arange(S, dtype=jnp.int32)[None]
    tt = spec.timetable()
    K = tt.n_slots
    zb = spec.schedule == "zerobubble"
    is_int8 = spec.wire_codec == "int8"

    # (P, K) tables baked as constants; [stage, t] gathers give each device
    # its compiled unit for the slot
    role_tbl = jnp.asarray(tt.role)
    micro_tbl = jnp.asarray(tt.micro)
    vst_tbl = jnp.asarray(tt.vstage)
    zarr_tbl = jnp.asarray(tt.z_arrive)
    zsrc_tbl = jnp.asarray(tt.z_src)
    garr_tbl = jnp.asarray(tt.g_arrive)
    gsrc_tbl = jnp.asarray(tt.g_src)

    def body(toks, labs, embed_tbl, unembed_tbl, final_gamma, stages):
        stages = jax.tree.map(lambda a: a[0], stages)
        B_loc = toks.shape[1]
        stage = jax.lax.axis_index("model")
        pos = jnp.broadcast_to(positions, (B_loc, S))
        last = Pn - 1
        pad_mask = (jnp.arange(unembed_tbl.shape[0]) >= cfg.vocab_size
                    ) * (-1e9)

        code_shape = (B_loc, S, d_wire)
        if is_int8:
            n_code = B_loc * S * d_wire
            blk_w = qs.wire_block(n_code, d_wire)

            def wire_zero():
                return (jnp.zeros(code_shape, jnp.int8),
                        jnp.zeros((n_code // blk_w,), jnp.float32))

            def wire_pack(z_f):
                # f32 code -> the physically shipped/stashed (q, scales)
                return ops.wire_encode(z_f)

            def wire_unpack(wz):
                # exact dequantized f32 (== ops.int8_wire_roundtrip output)
                return ops.wire_decode(*wz)
        else:
            def wire_zero():
                return jnp.zeros(code_shape, spec.carry_dtype())

            def wire_pack(z_f):
                return z_f

            def wire_unpack(wz):
                return wz

        def ring_zero(n):
            return jax.tree.map(
                lambda a: jnp.zeros((n,) + a.shape, a.dtype), wire_zero())

        def ring_read(ring, i):
            return jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, 0,
                                                       keepdims=False), ring)

        def ring_write(ring, val, i, ok):
            def upd(a, v):
                cur = jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
                return jax.lax.dynamic_update_index_in_dim(
                    a, jnp.where(ok, v, cur), i, 0)
            return jax.tree.map(upd, ring, val)

        def chunk_params(v_idx):
            if V == 1:
                return stages
            return jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, v_idx, 0,
                                                       keepdims=False),
                stages)

        def acc_chunk_grads(g_acc, g_chunk, v_idx):
            if V == 1:
                return jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), g_acc, g_chunk)

            def upd(a, g):
                cur = jax.lax.dynamic_index_in_dim(a, v_idx, 0,
                                                   keepdims=False)
                return jax.lax.dynamic_update_index_in_dim(
                    a, cur + g.astype(jnp.float32), v_idx, 0)
            return jax.tree.map(upd, g_acc, g_chunk)

        def stage_fn(chunk_p, z_in, emb, unemb, fgamma, toks_t, labs_t,
                     is_first):
            """One chunk's forward from its received wire code (or tokens
            on the first chunk), through its blocks, to its exit code AND
            the loss head — one function so one vjp yields every cotangent;
            the where() gates route grads to the right owners (embed on the
            first chunk, head params on the last) automatically."""
            x_e = jnp.take(emb, toks_t, axis=0).astype(compute_dtype)
            r = _decode_boundary(z_in, chunk_p, spec, compute_dtype)
            x = jnp.where(is_first, x_e, r)
            x = _stage_forward(chunk_p["blocks"], x, cfg, kind, pos, False)
            z_out = _encode_boundary(x, chunk_p, cfg, spec, codec=False)
            h = rmsnorm(x, fgamma, cfg.norm_eps)
            lgts = jnp.einsum("bsd,vd->bsv", h.astype(jnp.float32),
                              unemb.astype(jnp.float32)) + pad_mask
            loss_t = next_token_loss(lgts, labs_t, z_loss)
            return z_out, loss_t

        def slot(carry, t):
            z_wire, g_wire, z_ring, g_ring, grads, loss_acc = carry
            # ---- arrivals: last slot's hand-offs enter their compiled
            # ring slots (at the warmup->steady seam a code arrives up to
            # P - s slots before its forward slot, so it must be stashed on
            # arrival — a single-slot register would lose it)
            za = zarr_tbl[stage, t]
            z_ring = ring_write(z_ring, z_wire, jnp.maximum(za, 0), za >= 0)
            ga = garr_tbl[stage, t]
            g_ring = ring_write(g_ring, g_wire, jnp.maximum(ga, 0), ga >= 0)
            # ---- this slot's compiled unit ----
            role_id = role_tbl[stage, t]
            m_idx = micro_tbl[stage, t]
            v_idx = vst_tbl[stage, t]
            z_src = ring_read(z_ring, zsrc_tbl[stage, t])
            ct_src = ring_read(g_ring, gsrc_tbl[stage, t])
            toks_t = jax.lax.dynamic_index_in_dim(toks, m_idx, 0,
                                                  keepdims=False)
            labs_t = jax.lax.dynamic_index_in_dim(labs, m_idx, 0,
                                                  keepdims=False)
            chunk_p = chunk_params(v_idx)
            is_first = (stage == 0) & (v_idx == 0)
            is_last = (stage == last) & (v_idx == V - 1)

            def seed_cts(z_out, loss_t):
                """Cotangent seeds: the final chunk seeds its loss with 1,
                everyone else the ring-held upstream activation grad."""
                ct_z = jnp.where(is_last, jnp.zeros_like(z_out),
                                 wire_unpack(ct_src).astype(z_out.dtype))
                ct_loss = jnp.where(is_last, jnp.ones_like(loss_t),
                                    jnp.zeros_like(loss_t))
                return ct_z, ct_loss

            def gate_g(g_send):
                # the first chunk has no upstream; with wraparound perms
                # (V > 1) its send would otherwise corrupt the last device
                return jax.tree.map(
                    lambda a: jnp.where(is_first, jnp.zeros_like(a), a),
                    g_send)

            # ---- role dispatch: pay only for what this slot does --------
            # (branches close over loop-invariant tracers; no collectives
            # inside, so per-device switch is shard_map-legal)
            def idle(grads, loss_acc):
                return wire_zero(), wire_zero(), grads, loss_acc

            def fwd_slot(grads, loss_acc):
                # primal blocks only: no loss head, no pullback
                x_e = jnp.take(embed_tbl, toks_t,
                               axis=0).astype(compute_dtype)
                r = _decode_boundary(wire_unpack(z_src), chunk_p, spec,
                                     compute_dtype)
                x = jnp.where(is_first, x_e, r)
                x = _stage_forward(chunk_p["blocks"], x, cfg, kind, pos,
                                   False)
                z_out = _encode_boundary(x, chunk_p, cfg, spec, codec=False)
                return wire_pack(z_out), wire_zero(), grads, loss_acc

            def bwd_full(grads, loss_acc):
                z_in = wire_unpack(z_src)
                (z_out, loss_t), vjp = jax.vjp(
                    lambda cp, z, e, u, fg: stage_fn(cp, z, e, u, fg,
                                                     toks_t, labs_t,
                                                     is_first),
                    chunk_p, z_in, embed_tbl, unembed_tbl, final_gamma)
                ct_z, ct_loss = seed_cts(z_out, loss_t)
                g_cp, g_z, g_emb, g_unemb, g_fg = vjp((ct_z, ct_loss))
                grads = (acc_chunk_grads(grads[0], g_cp, v_idx),
                         grads[1] + g_emb.astype(jnp.float32),
                         grads[2] + g_unemb.astype(jnp.float32),
                         grads[3] + g_fg.astype(jnp.float32))
                g_send = gate_g(wire_pack(g_z.astype(spec.carry_dtype())))
                loss_acc = loss_acc + jnp.where(is_last, loss_t,
                                                jnp.zeros_like(loss_t))
                return wire_zero(), g_send, grads, loss_acc

            def bwd_act(grads, loss_acc):
                # zerobubble B: activation grad only — the upstream
                # hand-off leaves as early as 1F1B's; params wait for W
                z_in = wire_unpack(z_src)
                (z_out, loss_t), vjp = jax.vjp(
                    lambda z: stage_fn(chunk_p, z, embed_tbl, unembed_tbl,
                                       final_gamma, toks_t, labs_t,
                                       is_first),
                    z_in)
                ct_z, ct_loss = seed_cts(z_out, loss_t)
                (g_z,) = vjp((ct_z, ct_loss))
                g_send = gate_g(wire_pack(g_z.astype(spec.carry_dtype())))
                loss_acc = loss_acc + jnp.where(is_last, loss_t,
                                                jnp.zeros_like(loss_t))
                return wire_zero(), g_send, grads, loss_acc

            def wgrad_slot(grads, loss_acc):
                # zerobubble W: the same vjp restricted to params, run in a
                # former idle slot; the cotangent ring kept the seed alive
                z_in = wire_unpack(z_src)
                (z_out, loss_t), vjp = jax.vjp(
                    lambda cp, e, u, fg: stage_fn(cp, z_in, e, u, fg,
                                                  toks_t, labs_t, is_first),
                    chunk_p, embed_tbl, unembed_tbl, final_gamma)
                ct_z, ct_loss = seed_cts(z_out, loss_t)
                g_cp, g_emb, g_unemb, g_fg = vjp((ct_z, ct_loss))
                grads = (acc_chunk_grads(grads[0], g_cp, v_idx),
                         grads[1] + g_emb.astype(jnp.float32),
                         grads[2] + g_unemb.astype(jnp.float32),
                         grads[3] + g_fg.astype(jnp.float32))
                return wire_zero(), wire_zero(), grads, loss_acc

            branches = ([idle, fwd_slot, bwd_act, wgrad_slot] if zb
                        else [idle, fwd_slot, bwd_full])
            z_send, g_send, grads, loss_acc = jax.lax.switch(
                role_id, branches, grads, loss_acc)
            # ---- hand-offs: consumed exactly one slot later; chunk
            # boundaries wrap devices only when V > 1 ----------------------
            if V == 1:
                fperm = [(i, i + 1) for i in range(Pn - 1)]
                bperm = [(i + 1, i) for i in range(Pn - 1)]
            else:
                fperm = [(i, (i + 1) % Pn) for i in range(Pn)]
                bperm = [((i + 1) % Pn, i) for i in range(Pn)]
            z_wire = jax.tree.map(
                lambda a: jax.lax.ppermute(a, "model", fperm), z_send)
            g_wire = jax.tree.map(
                lambda a: jax.lax.ppermute(a, "model", bperm), g_send)
            return (z_wire, g_wire, z_ring, g_ring, grads, loss_acc), None

        grads0 = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                              (stages, embed_tbl, unembed_tbl, final_gamma))
        carry0 = (wire_zero(), wire_zero(), ring_zero(tt.z_ring),
                  ring_zero(tt.g_ring), grads0, _traced_zero(toks))
        (_, _, _, _, grads, loss_acc), _ = jax.lax.scan(
            slot, carry0, jnp.arange(K, dtype=jnp.int32))

        g_stages, g_emb, g_unemb, g_fg = grads
        scale = 1.0 / M
        loss = jax.lax.pmean(
            jax.lax.psum(jnp.where(stage == last, loss_acc, 0.0 * loss_acc),
                         "model") * scale, batch_axes)
        # stage params: per-stage owner; shared params: sum over stages
        g_stages = jax.tree.map(
            lambda a: jax.lax.pmean(a * scale, batch_axes)[None], g_stages)
        shared = jax.tree.map(
            lambda a: jax.lax.pmean(jax.lax.psum(a * scale, "model"),
                                    batch_axes),
            (g_emb, g_unemb, g_fg))
        return loss, g_stages, *shared

    stage_specs = jax.tree.map(lambda _: P("model"), params["stages"])
    tied = "unembed" not in params["embeds"]
    unembed = params["embeds"].get("unembed", params["embeds"]["embed"])
    loss, g_stages, g_emb, g_unemb, g_fg = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, batch_axes, None), P(None, batch_axes, None),
                  P(None, None), P(None, None), P(None), stage_specs),
        out_specs=(P(), stage_specs, P(), P(), P()), check_vma=False,
    )(tokens_m, labels_m, params["embeds"]["embed"], unembed,
      params["final_norm"], params["stages"])

    embeds_g = {"embed": g_emb + g_unemb if tied else g_emb}
    if not tied:
        embeds_g["unembed"] = g_unemb
    grads = {"embeds": embeds_g, "final_norm": g_fg, "stages": g_stages}
    return loss, grads


def pipeline_1f1b_grads(params, batch, cfg: ModelConfig, spec: PipelineSpec,
                        mesh, batch_axes: tuple[str, ...] = ("data",),
                        z_loss: float = 1e-4, compute_dtype=jnp.bfloat16):
    """Back-compat name for the generalized executor (PR 2/6 API)."""
    return pipeline_timetable_grads(params, batch, cfg, spec, mesh,
                                    batch_axes, z_loss, compute_dtype)


def pipeline_loss_1f1b(params, batch, cfg: ModelConfig, spec: PipelineSpec,
                       mesh, batch_axes: tuple[str, ...] = ("data",),
                       z_loss: float = 1e-4, compute_dtype=jnp.bfloat16):
    """`jax.grad`-compatible explicit-schedule loss: the slot executor
    computes the gradients in its own forward pass, so the custom_vjp
    backward just hands them to autodiff (scaled by the incoming
    cotangent).  Works for any executor schedule (1f1b / interleaved /
    zerobubble)."""

    @jax.custom_vjp
    def run(p):
        loss, _ = pipeline_timetable_grads(p, batch, cfg, spec, mesh,
                                           batch_axes, z_loss, compute_dtype)
        return loss

    def fwd(p):
        loss, grads = pipeline_timetable_grads(p, batch, cfg, spec, mesh,
                                               batch_axes, z_loss,
                                               compute_dtype)
        return loss, (grads, p)

    def bwd(res, g):
        grads, p = res
        return (jax.tree.map(
            lambda gr, pp: (g * gr.astype(jnp.float32)).astype(pp.dtype),
            grads, p),)

    run.defvjp(fwd, bwd)
    return run(params)


def pipeline_loss_and_grads(params, batch, cfg: ModelConfig,
                            spec: PipelineSpec, mesh,
                            batch_axes: tuple[str, ...] = ("data",),
                            z_loss: float = 1e-4,
                            compute_dtype=jnp.bfloat16):
    """Schedule dispatcher for the training hot path: GPipe differentiates
    the tick scan; every other schedule replays its compiled timetable in
    the slot executor, computing grads explicitly in one pass."""
    if spec.schedule == "gpipe":
        return jax.value_and_grad(
            lambda p: pipeline_loss_fused(p, batch, cfg, spec, mesh,
                                          batch_axes, z_loss,
                                          compute_dtype))(params)
    return pipeline_timetable_grads(params, batch, cfg, spec, mesh,
                                    batch_axes, z_loss, compute_dtype)
