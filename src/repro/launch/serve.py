"""Serving: the dense single-process path + the swarm decode pipeline.

Three entry points, one token stream (docs/SERVE.md):

  generate        dense ``Model`` prefill + decode with the paged-ish KV
                  cache — the single-process reference path (one jitted
                  ``decode_step`` reused for the prefill chunk and every
                  decode step; re-tracing is per-shape, so the two shapes
                  coexist in one compilation cache).
  swarm_generate  the sequential *oracle* for the stage-sharded serve
                  plane: each request runs alone through every
                  ``StageProgram`` in stage order — same stage params,
                  same boundary codec round-trips, same sampling keys as
                  the pipelined driver, with none of the pipelining.
  serve_swarm     the real thing: ``ServeDriver`` running the compiled
                  decode timetable with continuous batching over an
                  in-process store, a socket store, or a spawned
                  ``ServeActor`` fleet (``transport="actors"``).

Greedy parity contract: at the same seed, ``serve_swarm`` emits tokens
bit-identical to ``swarm_generate`` for every transport, stage count and
admission order (tests/test_serve.py pins it).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --requests 4 --prompt-len 32 --max-new 16
  PYTHONPATH=src python -m repro.launch.serve --smoke --swarm --stages 2 \
      --lanes 2 --transport actors
"""
from __future__ import annotations

import argparse
import time
from typing import Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import build_model


def generate(model, params, prompts: jax.Array, max_new: int,
             temperature: float = 0.0, seed: int = 0):
    """prompts (B, S) int32 -> (B, S+max_new) greedy/temp sampled tokens.

    Prefill populates the KV cache (cache written during one decode_step
    per prompt chunk); decode appends one token at a time.
    """
    B, S = prompts.shape
    state = model.init_decode_state(B, S + max_new)

    # one jitted callable for the prompt chunk *and* the token steps:
    # jit caches per input shape, so the (B, S) prefill trace and the
    # (B, 1) decode trace share the cache instead of each call paying a
    # fresh wrapper
    step_fn = jax.jit(model.decode_step)
    lgts, state = step_fn(params, state, {"tokens": prompts})
    tokens = prompts
    key = jax.random.key(seed)
    last = lgts[:, -1, :]
    for i in range(max_new):
        if temperature > 0:
            key, sub = jax.random.split(key)
            nxt = jax.random.categorical(sub, last / temperature, axis=-1)
        else:
            nxt = jnp.argmax(last, axis=-1)
        nxt = nxt.astype(jnp.int32)[:, None]
        tokens = jnp.concatenate([tokens, nxt], axis=1)
        lgts, state = step_fn(params, state, {"tokens": nxt})
        last = lgts[:, -1, :]
    return tokens


# ---------------------------------------------------------------------------
# swarm serve plane: oracle + driver front-end (docs/SERVE.md)
# ---------------------------------------------------------------------------


def swarm_generate(spec, seed: int, requests: Iterable,
                   *, wire_codec: str = "none") -> dict:
    """Sequential oracle for the stage-sharded serve plane.

    Each request runs alone, token by token, through every stage in
    order: prefill the whole prompt at step 0, then one ``decode_step``
    per emitted token, crossing each stage boundary through the *same*
    ``encode_wire``/``decode_wire`` round-trip the store path uses and
    sampling with the same ``request_key(seed, req, index)`` fold.  The
    pipelined ``ServeDriver`` must match this stream bit-for-bit at
    temperature 0.  Returns ``{req: [token, ...]}``.
    """
    from repro.runtime import stage_model as sm

    P = spec.n_stages
    programs = [sm.StageProgram(spec, s, wire_codec) for s in range(P)]
    params = [sm.serve_stage_params(spec, seed, s) for s in range(P)]
    out: dict = {}
    for r in requests:
        prompt = np.asarray(r.prompt, np.int32).reshape(1, -1)
        caches = [programs[s].init_cache(1, prompt.shape[1] + r.max_new)
                  for s in range(P)]
        toks: list = []
        for i in range(r.max_new):
            h = jnp.asarray(prompt) if i == 0 \
                else jnp.asarray([[toks[-1]]], jnp.int32)
            for s in range(P):
                h, caches[s] = programs[s].decode_step(params[s], h,
                                                       caches[s])
                if s < P - 1:
                    h = programs[s].decode_wire(programs[s].encode_wire(h))
            logits = jnp.asarray(h[:, -1], jnp.float32)
            toks.append(int(np.asarray(sm.sample_token(
                logits, temperature=r.temperature,
                key=sm.request_key(seed, r.req, i)))[0]))
        out[r.req] = toks
    return out


def build_servers(spec, seed: int, *, n_lanes: int, max_len: int,
                  wire_codec: str = "none") -> list:
    """One ``StageServer`` per stage with params re-derived from the
    session seed — the same derivation ``ServeActor`` runs remotely."""
    from repro.api.phases import StageServer
    from repro.runtime import stage_model as sm

    return [StageServer(spec, s, sm.serve_stage_params(spec, seed, s),
                        n_lanes=n_lanes, max_len=max_len,
                        wire_codec=wire_codec)
            for s in range(spec.n_stages)]


def serve_swarm(spec, requests: list, *, n_lanes: int, max_len: int,
                transport: str = "inprocess",
                store_address: Optional[tuple] = None, seed: int = 0,
                wire_codec: str = "none", timeout: float = 120.0) -> dict:
    """Serve ``requests`` over the decode pipeline on the chosen
    transport; returns ``{req: RequestRecord}``.

    ``inprocess``  in-memory store, driver executes every timetable slot.
    ``socket``     real ``StoreServer`` (spawned here unless
                   ``store_address`` points at a running one), driver
                   still executes the slots — every payload crosses the
                   wire.
    ``actors``     one spawned ``ServeActor`` process per stage against
                   the socket store; the driver only publishes plans,
                   samples and collects.
    """
    from repro.api.keys import KeySchema
    from repro.api.phases import ServeDriver
    from repro.api.transport import InProcessTransport, SocketTransport

    schema = KeySchema(version=5)
    if transport == "inprocess":
        driver = ServeDriver(
            spec, InProcessTransport(schema=schema), n_lanes=n_lanes,
            max_len=max_len, seed=seed, wire_codec=wire_codec,
            timeout=timeout,
            servers=build_servers(spec, seed, n_lanes=n_lanes,
                                  max_len=max_len, wire_codec=wire_codec))
        return driver.run(requests)

    if transport not in ("socket", "actors"):
        raise ValueError(f"unknown serve transport {transport!r}")

    from repro.runtime.store_server import StoreServer

    server = None
    if store_address is None:
        server = StoreServer().start()
        store_address = server.address
    store_address = (str(store_address[0]), int(store_address[1]))
    tp = SocketTransport(store_address, schema=schema)
    supervisor = None
    try:
        if transport == "socket":
            servers = build_servers(spec, seed, n_lanes=n_lanes,
                                    max_len=max_len, wire_codec=wire_codec)
        else:
            servers = None
            supervisor = _spawn_serve_fleet(spec, store_address, seed,
                                            wire_codec)
        driver = ServeDriver(spec, tp, n_lanes=n_lanes, max_len=max_len,
                             servers=servers, seed=seed,
                             wire_codec=wire_codec, timeout=timeout)
        records = driver.run(requests)
        if supervisor is not None:
            driver.stop_fleet()
            supervisor.join_all()
        return records
    finally:
        if supervisor is not None:
            supervisor.terminate_all()
        tp.close()
        if server is not None:
            server.stop()


def _spawn_serve_fleet(spec, store_address: tuple, seed: int,
                       wire_codec: str):
    """One ``ServeActor`` process per stage.  The spec carries only the
    session's shape; params re-derive from the seed in the serve plan."""
    from repro.api.config import SwarmConfig
    from repro.configs.base import TrainConfig
    from repro.runtime.actor import ActorSpec, ActorSupervisor

    swarm_cfg = SwarmConfig(n_stages=spec.n_stages, compress=spec.compress,
                            bottleneck_dim=spec.bottleneck_dim,
                            wire_codec=wire_codec, seed=seed)
    sup = ActorSupervisor()
    sup.spawn([ActorSpec(kind="server", uid=s, stage=s, model_cfg=spec.cfg,
                         config=swarm_cfg, train_cfg=TrainConfig(),
                         store_address=store_address)
               for s in range(spec.n_stages)])
    return sup


def _summarize(records: dict, t0: float, t1: float) -> None:
    n_tok = sum(len(r.tokens) for r in records.values())
    ttfts = sorted(r.ttft for r in records.values() if r.ttft is not None)
    print(f"served {len(records)} requests, {n_tok} tokens in "
          f"{t1 - t0:.2f}s ({n_tok / (t1 - t0):.1f} tok/s), "
          f"median ttft {ttfts[len(ttfts) // 2] * 1e3:.1f}ms")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--swarm", action="store_true",
                    help="serve over the stage-sharded decode pipeline "
                         "instead of the dense single-process model")
    ap.add_argument("--stages", type=int, default=2)
    ap.add_argument("--lanes", type=int, default=2)
    ap.add_argument("--transport", default="inprocess",
                    choices=("inprocess", "socket", "actors"))
    ap.add_argument("--store-address", default=None, metavar="HOST:PORT",
                    help="already-running store server (socket/actors); "
                         "default spawns one in-process")
    ap.add_argument("--wire-codec", default="none",
                    choices=("none", "int8"))
    ap.add_argument("--no-parity-check", action="store_true",
                    help="skip the greedy-parity check against the "
                         "sequential oracle (swarm mode, temperature 0)")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = configs.get(args.arch)
    if args.smoke:
        cfg = configs.smoke_variant(cfg)

    if not args.swarm:
        model = build_model(cfg)
        params = model.init(jax.random.key(args.seed))
        prompts = jax.random.randint(
            jax.random.key(args.seed + 1),
            (args.requests, args.prompt_len), 3, cfg.model.vocab_size,
            jnp.int32)
        t0 = time.perf_counter()
        out = generate(model, params, prompts, args.max_new,
                       args.temperature, args.seed)
        dt = time.perf_counter() - t0
        new_tokens = args.requests * args.max_new
        print(f"served {args.requests} requests, {new_tokens} new tokens "
              f"in {dt:.2f}s ({new_tokens/dt:.1f} tok/s)")
        print("sample completion token ids:",
              np.asarray(out[0, -args.max_new:]))
        return out

    from repro.api.phases import ServeRequest
    from repro.runtime import stage_model as sm

    assert cfg.model.n_layers % args.stages == 0, \
        "--stages must divide the model's layer count"
    spec = sm.SwarmModelSpec(cfg.model, args.stages)
    prompts = jax.random.randint(
        jax.random.key(args.seed + 1),
        (args.requests, args.prompt_len), 3, cfg.model.vocab_size,
        jnp.int32)
    requests = [ServeRequest(req=i, prompt=np.asarray(prompts[i]),
                             max_new=args.max_new,
                             temperature=args.temperature)
                for i in range(args.requests)]
    store_address = None
    if args.store_address:
        host, _, port = args.store_address.rpartition(":")
        store_address = (host, int(port))
    t0 = time.perf_counter()
    records = serve_swarm(
        spec, requests, n_lanes=args.lanes,
        max_len=args.prompt_len + args.max_new,
        transport=args.transport, store_address=store_address,
        seed=args.seed, wire_codec=args.wire_codec)
    t1 = time.perf_counter()
    _summarize(records, t0, t1)
    if args.temperature <= 0 and not args.no_parity_check:
        oracle = swarm_generate(spec, args.seed, requests,
                                wire_codec=args.wire_codec)
        for i in sorted(records):
            assert records[i].tokens == oracle[i], \
                f"parity violation on request {i}"
        print(f"greedy parity vs sequential oracle: OK "
              f"({len(records)} requests, transport={args.transport})")
    return records


if __name__ == "__main__":
    main()
