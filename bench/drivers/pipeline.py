"""Driver of the on-mesh pipeline: the training step of ``repro.core.pipeline``
as ``launch/train.py --strategy pipeline`` builds it.

Parameters are stage-stacked over a ("data", "model") mesh of the cell's
chips and made there in one jitted call from the seed; one jitted step runs
the schedule's loss and gradients and the SGD update of ``launch/train.py``,
with the old parameters donated to the new.  Set-up runs one step, which
compiles; the window runs steps until its time is up and waits for the
last.

No cell runs this driver yet.  The plain reference of the pipeline (stage
by stage, since the whole model does not fit one chip) is not written, so
``check`` gives a number that fails: a cell on this driver reads
``correct`` false until it is.
"""
from __future__ import annotations

import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.drivers.swarm import model_config
from bench.lib import flops
from bench.lib.tokens import TokenBatches


def build_step(config_name: str, m: dict, t: dict, mesh):
    """(init, shardings, step) of the cell: ``init(key)`` makes the
    parameters, ``shardings`` is their layout on ``mesh``, and
    ``step(params, batch) -> (params, loss)`` is jitted."""
    from repro.core.pipeline import (PipelineSpec, init_pipeline_params,
                                     pipeline_loss_and_grads,
                                     pipeline_param_shardings)
    cfg = model_config(config_name, m)
    spec = PipelineSpec(n_stages=t["n_stages"],
                        n_microbatches=t["microbatches"], compress=True,
                        bottleneck_dim=t["bottleneck_dim"],
                        schedule=t["schedule"], wire_codec=t["wire_codec"])
    init = functools.partial(init_pipeline_params, cfg=cfg, spec=spec)
    shardings = pipeline_param_shardings(
        jax.eval_shape(init, jax.random.key(0)), mesh)
    lr = t["lr"]

    def step(params, batch):
        loss, grads = pipeline_loss_and_grads(params, batch, cfg, spec, mesh)
        new = jax.tree.map(
            lambda p, g: (p - lr * g.astype(jnp.float32)).astype(p.dtype),
            params, grads)
        return new, loss

    return init, shardings, jax.jit(step, donate_argnums=0)


def make_mesh(devices, t: dict):
    from jax.sharding import Mesh
    n = t["n_stages"]
    return Mesh(np.asarray(devices).reshape(len(devices) // n, n),
                ("data", "model"))


class PipelineCell:
    end_to_end = "pipeline_tokens_per_s"

    def __init__(self, ctx: dict):
        self.ctx = ctx
        self.seed = ctx["seed"]
        self.m = ctx["config"]["model"]
        self.t = ctx["traffic"]

    def setup(self) -> None:
        from jax.sharding import NamedSharding, PartitionSpec
        t = self.t
        self.mesh = make_mesh(jax.devices()[: self.ctx["chips"]], t)
        init, shardings, self.step = build_step(self.ctx["config_name"],
                                                self.m, t, self.mesh)
        key = jax.random.fold_in(jax.random.key(self.seed // 2**32),
                                 self.seed % 2**32)
        self.params = jax.jit(init, out_shardings=shardings)(key)
        self.batch_sharding = NamedSharding(self.mesh, PartitionSpec())
        self.corpus = TokenBatches(self.seed, self.m["vocab_size"],
                                   t["batch_size"], t["seq_len"],
                                   t["zipf_exponent"])
        self.tick = 0
        self.params, loss = self.step(self.params, self._batch())
        self.first_loss = float(loss)

    def _batch(self) -> dict:
        b = jax.device_put(self.corpus.batch(self.tick), self.batch_sharding)
        self.tick += 1
        return b

    def window(self, seconds: float) -> dict:
        t = self.t
        steps = 0
        start = time.perf_counter()
        self.window_start_ns = time.perf_counter_ns()
        while time.perf_counter() - start < seconds:
            self.params, loss = self.step(self.params, self._batch())
            loss.block_until_ready()
            steps += 1
        jax.block_until_ready(self.params)
        window_s = time.perf_counter() - start
        tokens = steps * t["batch_size"] * t["seq_len"]
        self.readings = {"tokens": tokens, "steps": steps,
                         "window_seconds": window_s}
        return {"attempted": steps, "failed": 0,
                "metrics": {self.end_to_end: tokens / window_s}}

    def context(self) -> dict:
        m, t = self.m, self.t
        return dict(
            self.readings, window_start_ns=self.window_start_ns, model=m,
            flops_per_token=flops.train_flops_per_token(
                m, m["num_hidden_layers"], t["seq_len"], t["bottleneck_dim"],
                t["n_stages"] - 1),
            attention=dict(batch=t["batch_size"] // t["microbatches"],
                           seq=t["seq_len"],
                           heads=m["num_attention_heads"],
                           kv_heads=m["num_key_value_heads"],
                           head_dim=m["head_dim"]))

    def release(self) -> None:
        del self.params, self.step
        gc.collect()

    def check(self, limits: dict) -> list:
        return [["reference_written", float("nan"), 0.0]]


def build(ctx: dict) -> PipelineCell:
    return PipelineCell(ctx)
