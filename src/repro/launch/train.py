"""Training driver: single-host end-to-end loop with fault tolerance.

Runs any arch (full or --smoke reduced config) on the synthetic corpus with:
  * checkpoint/restart (atomic + async, integrity-verified; --resume picks
    up the latest step, including the data cursor),
  * optional preemption simulation (--kill-at-step N exits mid-run; rerun
    with --resume to prove recovery),
  * metrics log (loss/grad-norm/steps-per-sec) to stdout + jsonl.

On a real pod the same ``Model.train_step`` lowers under the production
mesh (see dryrun.py); this driver is the CPU-scale harness used by the
examples and integration tests.

``--strategy pipeline`` drives the ``repro.core.pipeline`` engine instead:
stages shard over the devices' ``model`` axis (forced host devices work —
set XLA_FLAGS=--xla_force_host_platform_device_count=N *before* launch),
with the schedule (``gpipe``/``1f1b``/``interleaved``/``zerobubble``),
virtual-stage count and wire codec (``none``/``int8``) selectable per
docs/PERF.md.  The first metrics record carries the static
schedule accounting (wire bytes per hop, bubble fraction, stash bytes).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
      --steps 200 --batch-size 8 --seq-len 128 --ckpt-dir /tmp/ckpt --resume
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
      --strategy pipeline --pipeline-schedule 1f1b --wire-codec int8 \
      --steps 40 --batch-size 8 --seq-len 32
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint import CheckpointManager
from repro.core.pipeline import SCHEDULES
from repro.data.pipeline import DataConfig, SyntheticCorpus
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import build_model


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--kill-at-step", type=int, default=None,
                    help="simulate preemption: hard-exit at this step")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-out", default=None)
    # --strategy pipeline knobs (repro.core.pipeline engine)
    ap.add_argument("--strategy", default="tensor",
                    choices=["tensor", "pipeline"])
    ap.add_argument("--pipeline-stages", type=int, default=None,
                    help="stage count (default: all visible devices)")
    ap.add_argument("--pipeline-microbatches", type=int, default=None)
    ap.add_argument("--pipeline-schedule", default="gpipe",
                    choices=list(SCHEDULES))
    ap.add_argument("--pipeline-virtual-stages", type=int, default=1,
                    help="virtual stages (model chunks) per device; >1 "
                         "requires --pipeline-schedule interleaved")
    ap.add_argument("--n-layers", type=int, default=None,
                    help="override layer count (must split evenly into "
                         "stages x virtual stages)")
    ap.add_argument("--wire-codec", default="none", choices=["none", "int8"])
    ap.add_argument("--bottleneck-dim", type=int, default=None)
    ap.add_argument("--no-compress", action="store_true",
                    help="stream full-width activations, not codes")
    ap.add_argument("--lr", type=float, default=0.1,
                    help="SGD lr for the pipeline strategy loop")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = configs.get(args.arch)
    if args.smoke:
        cfg = configs.smoke_variant(cfg)
    if args.strategy == "pipeline":
        return _pipeline_main(args, cfg)
    model = build_model(cfg)

    corpus = SyntheticCorpus(DataConfig(
        vocab_size=cfg.model.vocab_size, seq_len=args.seq_len,
        batch_size=args.batch_size, seed=args.seed))

    state = model.init_train_state(jax.random.key(args.seed))
    start_step = 0
    ckpt = None
    if args.ckpt_dir:
        ckpt = CheckpointManager(args.ckpt_dir, keep=3)
        if args.resume and ckpt.latest_step() is not None:
            state, meta = ckpt.restore(state)
            start_step = int(meta["step"])
            print(f"resumed from step {start_step} "
                  f"(data cursor restored with it)")

    step_fn = jax.jit(lambda s, b: model.train_step(s, b))
    metrics_log = []
    t0 = time.time()
    for step in range(start_step, args.steps):
        batch = {k: jnp.asarray(v)
                 for k, v in corpus.batch(step).items()}
        if cfg.model.family == "vlm" and cfg.model.frontend_tokens:
            from repro.models import frontends
            batch["vision_embeds"] = frontends.vision_patch_embeds(
                jax.random.fold_in(jax.random.key(7), step),
                args.batch_size, cfg.model.frontend_tokens, cfg.model.d_model)
        if cfg.model.family == "audio":
            from repro.models import frontends
            F = frontends.audio_frames_for_seq(args.seq_len)
            batch["frames"] = frontends.audio_frame_embeds(
                jax.random.fold_in(jax.random.key(8), step),
                args.batch_size, F, cfg.model.d_model)
        state, metrics = step_fn(state, batch)

        if args.kill_at_step is not None and step == args.kill_at_step:
            print(f"simulated preemption at step {step}", flush=True)
            os._exit(17)

        if ckpt and (step + 1) % args.ckpt_every == 0:
            ckpt.save(step + 1, state, {"arch": args.arch})
        if (step + 1) % args.log_every == 0 or step == args.steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            m.update(step=step + 1,
                     sps=round((step + 1 - start_step) / (time.time() - t0), 3))
            metrics_log.append(m)
            print(json.dumps(m), flush=True)

    if ckpt:
        ckpt.save(args.steps, state, {"arch": args.arch})
        ckpt.wait()
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            for m in metrics_log:
                f.write(json.dumps(m) + "\n")
    return metrics_log[-1] if metrics_log else {}


def _pipeline_main(args, cfg) -> dict:
    """Pipelined training loop: schedule + wire codec selectable, SGD on
    the stage-stacked param tree, static schedule stats in the first
    metrics record (benchmarks/bench_pipeline.py parses these)."""
    from repro.core.pipeline import (
        PipelineSpec,
        init_pipeline_params,
        pipeline_loss_and_grads,
        pipeline_param_shardings,
        schedule_stats,
    )
    assert not (args.ckpt_dir or args.resume
                or args.kill_at_step is not None), \
        "--strategy pipeline does not support checkpoint/preemption flags yet"
    mcfg = cfg.model
    if args.n_layers:
        import dataclasses
        mcfg = dataclasses.replace(mcfg, n_layers=args.n_layers)
    n_dev = jax.device_count()
    n_stages = args.pipeline_stages or n_dev
    n_chunks = n_stages * args.pipeline_virtual_stages
    assert n_dev % n_stages == 0, (n_dev, n_stages)
    assert mcfg.n_layers % n_chunks == 0, \
        f"{mcfg.n_layers} layers cannot split into {n_chunks} chunks"
    data_shards = n_dev // n_stages
    spec = PipelineSpec(
        n_stages=n_stages,
        n_microbatches=(args.pipeline_microbatches
                        or min(cfg.parallel.pipeline_microbatches,
                               args.batch_size)),
        compress=not args.no_compress,
        bottleneck_dim=(args.bottleneck_dim
                        or max(mcfg.bottleneck.bottleneck_dim // 2, 8)),
        schedule=args.pipeline_schedule,
        wire_codec=args.wire_codec,
        virtual_stages=args.pipeline_virtual_stages,
    )
    assert args.batch_size % (spec.n_microbatches * data_shards) == 0, \
        (args.batch_size, spec.n_microbatches, data_shards)
    mesh = jax.make_mesh((data_shards, n_stages), ("data", "model"))
    corpus = SyntheticCorpus(DataConfig(
        vocab_size=mcfg.vocab_size, seq_len=args.seq_len,
        batch_size=args.batch_size, seed=args.seed))
    # built under jit straight into its mesh layout: each device receives
    # its own stage slice, and no device ever holds the whole model
    init = functools.partial(init_pipeline_params, cfg=mcfg, spec=spec)
    key = jax.random.key(args.seed)
    params = jax.jit(init, out_shardings=pipeline_param_shardings(
        jax.eval_shape(init, key), mesh))(key)
    stats = schedule_stats(mcfg, spec, args.batch_size, args.seq_len,
                           data_shards=data_shards)
    # where the weights landed: each device's bytes of the param tree
    per_device: dict = {}
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] = (per_device.get(shard.device.id, 0)
                                           + shard.data.nbytes)
    stats["param_bytes_per_device"] = [per_device[d] for d in sorted(per_device)]

    @jax.jit
    def step_fn(params, batch):
        loss, grads = pipeline_loss_and_grads(params, batch, mcfg, spec,
                                              mesh)
        new_params = jax.tree.map(
            lambda p, g: (p - args.lr * g.astype(jnp.float32)
                          ).astype(p.dtype), params, grads)
        gnorm = jnp.sqrt(sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree.leaves(grads)))
        return new_params, {"loss": loss, "grad_norm": gnorm}

    metrics_log = [dict(stats, step=0)]
    print(json.dumps(metrics_log[0]), flush=True)
    t0 = time.time()
    step_seconds = []
    with mesh:
        for step in range(args.steps):
            batch = {k: jnp.asarray(v)
                     for k, v in corpus.batch(step).items()}
            ts = time.time()
            params, metrics = step_fn(params, batch)
            metrics = {k: float(v) for k, v in metrics.items()}
            step_seconds.append(time.time() - ts)
            if (step + 1) % args.log_every == 0 or step == args.steps - 1:
                m = dict(metrics, step=step + 1,
                         sps=round((step + 1) / (time.time() - t0), 3))
                metrics_log.append(m)
                print(json.dumps(m), flush=True)
    # median post-warmup step time — the bench's us_per_step
    tail = sorted(step_seconds[1:]) or step_seconds
    if tail:
        metrics_log[-1]["us_per_step"] = round(
            tail[len(tail) // 2] * 1e6, 1)
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            for m in metrics_log:
                f.write(json.dumps(m) + "\n")
    print(json.dumps({"final": metrics_log[-1]}), flush=True)
    return metrics_log[-1]


if __name__ == "__main__":
    main()
