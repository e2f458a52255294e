"""Mixture-of-Experts FFN with expert parallelism over the ``model`` axis.

Two execution paths, one algorithm:

* **EP path** (``ma`` with a mesh): ``shard_map`` over the full mesh.  Each
  device owns ``E_local = E / model_axis`` experts and its data-shard of
  tokens; it routes *its* tokens, keeps only assignments that land on local
  experts, runs a sort + ``jax.lax.ragged_dot`` grouped matmul, and psums the
  weighted expert outputs over the ``model`` axis.  No all-to-all of tokens is
  required: each token's top-k experts live somewhere on the model axis, and
  the psum both combines expert outputs and replicates the result — the same
  bytes an all-to-all-based EP would move, with a simpler schedule.

* **Local path** (``ma is None``): identical routing + ragged_dot with all
  experts local (CPU smoke tests, single device).

Capacity: per-device expert buffers are padded to
``cap = ceil(N_local * k * E_local / E * capacity_factor)`` rows; overflow
tokens are dropped (Switch-style), underflow rows ride along with gate 0.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.common import cdiv, round_up
from repro.configs.base import ModelConfig
from repro.models.layers import dense_init
from repro.sharding.partition import MeshAxes


def init_moe(key, cfg: ModelConfig) -> dict:
    assert cfg.moe is not None
    E = cfg.moe.n_experts
    ks = jax.random.split(key, 4)
    d, f = cfg.d_model, cfg.d_ff
    scale_in = 1.0 / np.sqrt(d)
    scale_out = 1.0 / np.sqrt(f * 2 * cfg.n_layers)
    return {
        "router": {"w": dense_init(ks[0], d, E, scale=0.02)},
        "experts": {
            "w_gate": (jax.random.truncated_normal(ks[1], -2, 2, (E, d, f)) * scale_in).astype(jnp.float32),
            "w_up": (jax.random.truncated_normal(ks[2], -2, 2, (E, d, f)) * scale_in).astype(jnp.float32),
            "w_out": (jax.random.truncated_normal(ks[3], -2, 2, (E, f, d)) * scale_out).astype(jnp.float32),
        },
    }


@functools.lru_cache(maxsize=None)
def _gathered_int8_fn(axis: str, gather_dim: int, scale_axis: int = -1):
    """FSDP all-gather of an expert-weight shard with int8 on the wire.

    §Perf cell A iteration 2 (beyond-paper, in the spirit of the paper's
    compressed-sharing stage): the per-microbatch expert-bank gathers
    dominate kimi-k2's collective term; quantizing the gather payload to
    int8 (per-row scales) halves the on-wire bytes vs bf16.  Backward is a
    straight-through estimator: the cotangent reduce-scatters back to the
    local shard at full precision (gradient fidelity preserved).
    """

    @jax.custom_vjp
    def f(w_local):
        return _fwd_impl(w_local)

    def _fwd_impl(w_local):
        wf = w_local.astype(jnp.float32)
        amax = jnp.max(jnp.abs(wf), axis=scale_axis, keepdims=True)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        w_q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
        w_q_g = jax.lax.all_gather(w_q, axis, axis=gather_dim, tiled=True)
        scale_g = jax.lax.all_gather(scale, axis, axis=gather_dim, tiled=True)
        return (w_q_g.astype(jnp.float32) * scale_g).astype(jnp.bfloat16)

    def fwd(w_local):
        return _fwd_impl(w_local), None

    def bwd(_, g):
        # reduce-scatter in the cotangent's own dtype (bf16 for the giant
        # archs — matching what GSPMD's transpose of a bf16 gather does)
        g_local = jax.lax.psum_scatter(
            g, axis, scatter_dimension=gather_dim, tiled=True)
        return (g_local,)

    f.defvjp(fwd, bwd)
    return f


def _route(x2d: jax.Array, router_w: jax.Array, top_k: int):
    """Top-k routing in fp32. Returns (ids (N,k) int32, gates (N,k) f32,

    aux_loss scalar) with gates renormalised over the selected k."""
    logits = x2d.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    # load-balancing auxiliary loss (Switch): E * sum_e f_e * p_e
    E = probs.shape[-1]
    me = jnp.mean(probs, axis=0)
    one_hot = jax.nn.one_hot(ids[:, 0], E, dtype=jnp.float32)
    ce = jnp.mean(one_hot, axis=0)
    aux = E * jnp.sum(me * ce)
    return ids, gates, aux


def _expert_ffn_local(
    x2d: jax.Array,             # (N, d) local tokens, compute dtype
    ids: jax.Array,             # (N, k)
    gates: jax.Array,           # (N, k) fp32
    w_gate: jax.Array,          # (E_local, d, f)
    w_up: jax.Array,
    w_out: jax.Array,           # (E_local, f, d)
    e_lo,                       # first local expert id (traced or 0)
    E_local: int,
    cap_per_expert: int,
) -> jax.Array:
    """Sort-by-expert + per-expert-capacity batched matmul over the local

    expert slice.  The (E_local, C, d) x (E_local, d, f) einsum lowers to a
    grouped/batched matmul on every backend with exactly E_local*C*d*f
    multiply-adds — unlike ragged_dot, whose CPU fallback loops over all
    groups (E_local x over-count, poisoning the dry-run roofline).
    Overflow beyond C tokens per expert is dropped Switch-style; empty slots
    ride along with gate 0.
    """
    N, k = ids.shape
    d = x2d.shape[1]
    C = cap_per_expert
    dtype = x2d.dtype
    flat_ids = ids.reshape(-1)
    flat_gates = gates.reshape(-1)
    tok_idx = jnp.repeat(jnp.arange(N, dtype=jnp.int32), k)
    local = (flat_ids >= e_lo) & (flat_ids < e_lo + E_local)
    sort_key = jnp.where(local, flat_ids - e_lo, E_local)   # non-local last
    order = jnp.argsort(sort_key, stable=True)
    s_exp = sort_key[order]                                  # (N*k,) sorted
    s_tok = tok_idx[order]
    s_gate = jnp.where(local, flat_gates, 0.0)[order]

    # position of each row within its expert group
    counts = jnp.bincount(s_exp, length=E_local + 1)
    starts = jnp.concatenate([jnp.zeros(1, counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(N * k, dtype=jnp.int32) - starts[s_exp].astype(jnp.int32)
    valid = (s_exp < E_local) & (pos < C)
    slot = jnp.where(valid, s_exp.astype(jnp.int32) * C + pos, E_local * C)

    # scatter token ids / gates into the (E_local*C,) slot grid
    tok_for_slot = jnp.zeros((E_local * C + 1,), jnp.int32).at[slot].set(
        s_tok, mode="drop")
    gate_for_slot = jnp.zeros((E_local * C + 1,), jnp.float32).at[slot].set(
        jnp.where(valid, s_gate, 0.0), mode="drop")
    tok_for_slot = tok_for_slot[:-1]
    gate_for_slot = gate_for_slot[:-1]

    xs = x2d[tok_for_slot].reshape(E_local, C, d)
    h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", xs, w_gate.astype(dtype)))
         * jnp.einsum("ecd,edf->ecf", xs, w_up.astype(dtype)))
    out = jnp.einsum("ecf,efd->ecd", h, w_out.astype(dtype))
    out = out * gate_for_slot.reshape(E_local, C, 1).astype(dtype)

    y = jnp.zeros((N, d), dtype)
    y = y.at[tok_for_slot.reshape(-1)].add(out.reshape(E_local * C, d),
                                           mode="drop")
    return y


def moe_ffn(params: dict, x: jax.Array, cfg: ModelConfig,
            ma: Optional[MeshAxes]) -> tuple[jax.Array, jax.Array]:
    """(B, S, d) -> (B, S, d); also returns the load-balancing aux loss."""
    assert cfg.moe is not None
    B, S, d = x.shape
    E, k = cfg.moe.n_experts, cfg.moe.top_k
    x2d = x.reshape(B * S, d)

    def cap_for(n_local: int) -> int:
        c = int(cdiv(n_local * k, E) * cfg.moe.capacity_factor) + 1
        return max(round_up(min(c, n_local * k), 4), 4)

    if ma is None or ma.mesh is None or ma.model_axis_size == 1:
        ids, gates, aux = _route(x2d, params["router"]["w"], k)
        y = _expert_ffn_local(
            x2d, ids, gates,
            params["experts"]["w_gate"], params["experts"]["w_up"],
            params["experts"]["w_out"], 0, E, cap_for(B * S))
        return y.reshape(B, S, d), aux

    # ---------------- EP path: shard_map over the whole mesh ----------------
    mesh = ma.mesh
    E_local = E // ma.model_axis_size
    n_batch_shards = int(np.prod([mesh.shape[a] for a in ma.batch]))
    shard_tokens = (B * S) % n_batch_shards == 0 and (B * S) >= n_batch_shards

    int8_gather = ma.fsdp and getattr(cfg.moe, "int8_fsdp_gather", False)

    if shard_tokens:
        # training/prefill: tokens sharded over batch axes, psum over model
        N_local = B * S // n_batch_shards
        cap = cap_for(N_local)

        def body(x_loc, router_w, w_gate, w_up, w_out):
            if int8_gather:
                # FSDP shards stay local; the gather rides int8 (§Perf A2)
                # per-f-row scales; the scale axis never coincides with
                # the gathered (FSDP) dim
                w_gate = _gathered_int8_fn(ma.data, 1, 2)(w_gate)
                w_up = _gathered_int8_fn(ma.data, 1, 2)(w_up)
                w_out = _gathered_int8_fn(ma.data, 2, 1)(w_out)
            ids, gates, aux = _route(x_loc, router_w, k)
            e_lo = jax.lax.axis_index(ma.model) * E_local
            y = _expert_ffn_local(x_loc, ids, gates, w_gate, w_up, w_out,
                                  e_lo, E_local, cap)
            y = jax.lax.psum(y, ma.model)
            aux = jax.lax.pmean(aux, ma.batch)
            return y, aux

        batch_sharded = P(ma.batch, None)
        if int8_gather:
            w_specs = (P(ma.model, ma.data, None), P(ma.model, ma.data, None),
                       P(ma.model, None, ma.data))
        else:
            w_specs = (P(ma.model, None, None), P(ma.model, None, None),
                       P(ma.model, None, None))
        y2d, aux = jax.shard_map(
            body, mesh=mesh,
            in_specs=(batch_sharded, P(None, None)) + w_specs,
            out_specs=(batch_sharded, P()), check_vma=False,
        )(x2d, params["router"]["w"], params["experts"]["w_gate"],
          params["experts"]["w_up"], params["experts"]["w_out"])
        return y2d.reshape(B, S, d), aux

    # decode / tiny batches: tokens replicated, experts sharded; every
    # device computes its local experts' contribution for ALL tokens
    cap = cap_for(B * S)

    def body_rep(x_all, router_w, w_gate, w_up, w_out):
        ids, gates, aux = _route(x_all, router_w, k)
        e_lo = jax.lax.axis_index(ma.model) * E_local
        y = _expert_ffn_local(x_all, ids, gates, w_gate, w_up, w_out,
                              e_lo, E_local, cap)
        return jax.lax.psum(y, ma.model), aux

    y2d, aux = jax.shard_map(
        body_rep, mesh=mesh,
        in_specs=(P(None, None), P(None, None), P(ma.model, None, None),
                  P(ma.model, None, None), P(ma.model, None, None)),
        out_specs=(P(None, None), P()), check_vma=False,
    )(x2d, params["router"]["w"], params["experts"]["w_gate"],
      params["experts"]["w_up"], params["experts"]["w_out"])
    return y2d.reshape(B, S, d), aux
