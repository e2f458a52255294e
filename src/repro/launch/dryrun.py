import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=512")))

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

THE lines above must execute before any other import (jax locks the
device count at first init).  The 512 forced host devices are CPU
devices, so the dry-run pins itself to the CPU and appends its flag to
any ``XLA_FLAGS`` already set.  Never import this module into a process
that is meant to use a chip.  This module proves the distribution config is
coherent without hardware: ``jax.jit(step).lower(**specs).compile()`` must
succeed for the 16x16 single-pod mesh and the 2x16x16 multi-pod mesh for
every assigned architecture and input shape, and the compiled artifact
feeds the §Roofline analysis (memory_analysis / cost_analysis / HLO
collective parsing).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
      --shape train_4k [--multi-pod] [--strategy tensor|pipeline] \
      [--out results.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun_all.json
"""
import argparse
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.configs.base import SHAPES, applicable_shapes
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.launch.shardings import (
    batch_specs,
    decode_state_spec_tree,
    named,
    train_state_specs,
)
from repro.models.model import build_model
from repro.sharding.partition import make_mesh_axes, param_specs


def _shape_structs(tree, spec_tree, mesh):
    """Attach NamedShardings to ShapeDtypeStructs."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def attach(sds, spec):
        sh = NamedSharding(mesh, spec) if isinstance(spec, P) else spec
        return jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=sh)

    return jax.tree.map(attach, tree, spec_tree,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def run_cell(arch_id: str, shape_name: str, multi_pod: bool = False,
             strategy: str = "tensor", verbose: bool = True) -> dict:
    """Lower + compile one cell; returns the roofline record."""
    cfg = configs.get(arch_id)
    shape = SHAPES[shape_name]
    if shape.name == "long_500k" and not cfg.model.sub_quadratic:
        return {"arch": arch_id, "shape": shape_name,
                "mesh": "multi_pod" if multi_pod else "single_pod",
                "status": "skipped",
                "reason": "long_500k requires sub-quadratic attention "
                          "(DESIGN.md shape rules)"}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    model = build_model(cfg)
    ma = make_mesh_axes(mesh, cfg.model, cfg.parallel)
    mesh_name = "multi_pod" if multi_pod else "single_pod"
    t0 = time.time()

    with mesh:
        if strategy == "pipeline":
            record = _lower_pipeline(cfg, model, shape, mesh, ma)
        elif shape.kind == "train":
            record = _lower_train(cfg, model, shape, mesh, ma)
        elif shape.kind == "prefill":
            record = _lower_prefill(cfg, model, shape, mesh, ma)
        else:
            record = _lower_decode(cfg, model, shape, mesh, ma)

    compiled, extra = record
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    mem_stats = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
        "output_bytes": getattr(mem, "output_size_in_bytes", 0),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
        "code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
    }
    mem_stats["total_bytes"] = (mem_stats["argument_bytes"]
                                + mem_stats["temp_bytes"]
                                + mem_stats["code_bytes"])
    score_dims = (shape.seq_len, shape.seq_len) if cfg.model.uses_attention \
        else None
    report = rl.analyze(
        arch_id, shape_name, mesh_name, chips, cost, hlo,
        rl.model_flops_for(cfg, shape, shape.kind == "train"), mem_stats,
        score_dims=score_dims)
    out = report.asdict()
    out.update(status="ok", compile_seconds=round(time.time() - t0, 1),
               strategy=strategy, **extra)
    if verbose:
        print(f"[{arch_id} x {shape_name} x {mesh_name} x {strategy}] "
              f"compiled in {out['compile_seconds']}s | "
              f"mem/device {mem_stats['total_bytes']/2**30:.2f} GiB | "
              f"t_comp {report.t_compute:.4f}s t_mem {report.t_memory:.4f}s "
              f"t_coll {report.t_collective:.4f}s -> {report.bottleneck}")
    return out


def _lower_train(cfg, model, shape, mesh, ma):
    state_shapes = model.abstract_train_state()
    state_specs = train_state_specs(model, ma)
    b_specs = batch_specs(model, shape, ma)
    batch_shapes = model.input_specs(shape)

    state_in = _shape_structs(state_shapes, state_specs, mesh)
    batch_in = _shape_structs(batch_shapes, b_specs, mesh)

    def step(state, batch):
        return model.train_step(state, batch, ma)

    state_sh = jax.tree.map(lambda s: jax.sharding.NamedSharding(mesh, s),
                            state_specs,
                            is_leaf=lambda x: isinstance(
                                x, jax.sharding.PartitionSpec))
    lowered = jax.jit(step, out_shardings=(state_sh, None),
                      donate_argnums=(0,)).lower(state_in, batch_in)
    return lowered.compile(), {}


def _lower_prefill(cfg, model, shape, mesh, ma):
    params_shapes = jax.eval_shape(model.init, jax.random.key(0))
    p_specs = param_specs(params_shapes, ma)
    params_in = _shape_structs(params_shapes, p_specs, mesh)
    b_specs = batch_specs(model, shape, ma)
    batch_in = _shape_structs(model.input_specs(shape), b_specs, mesh)

    def step(params, batch):
        return model.prefill_step(params, batch, ma)

    lowered = jax.jit(step).lower(params_in, batch_in)
    return lowered.compile(), {}


def _lower_decode(cfg, model, shape, mesh, ma):
    params_shapes = jax.eval_shape(model.init, jax.random.key(0))
    p_specs = param_specs(params_shapes, ma)
    params_in = _shape_structs(params_shapes, p_specs, mesh)

    state_shapes = model.decode_state_specs(shape)
    st_specs = decode_state_spec_tree(model, shape, ma)
    state_in = _shape_structs(state_shapes, st_specs, mesh)

    b_specs = batch_specs(model, shape, ma)
    batch_in = _shape_structs(model.input_specs(shape), b_specs, mesh)

    def step(params, dec_state, batch):
        return model.decode_step(params, dec_state, batch, ma)

    st_sh = jax.tree.map(lambda s: jax.sharding.NamedSharding(mesh, s),
                         st_specs,
                         is_leaf=lambda x: isinstance(
                             x, jax.sharding.PartitionSpec))
    lowered = jax.jit(step, out_shardings=(None, st_sh),
                      donate_argnums=(1,)).lower(params_in, state_in, batch_in)
    return lowered.compile(), {}


def _lower_pipeline(cfg, model, shape, mesh, ma):
    """Paper-faithful pipeline strategy (dense stacks; §Perf cell)."""
    from repro.core.pipeline import (
        PipelineSpec,
        init_pipeline_params,
        pipeline_loss,
        pipeline_loss_and_grads,
        pipeline_loss_fused,
    )
    assert shape.kind == "train", "pipeline strategy lowers train_step"
    n_stages = mesh.shape["model"]
    compress = os.environ.get("REPRO_PIPELINE_COMPRESS", "1") == "1"
    spec = PipelineSpec(
        n_stages=n_stages,
        n_microbatches=int(os.environ.get(
            "REPRO_PIPELINE_MICROBATCHES",
            str(cfg.parallel.pipeline_microbatches))),
        compress=compress,
        bottleneck_dim=max(cfg.model.bottleneck.bottleneck_dim, 32),
        schedule=os.environ.get("REPRO_PIPELINE_SCHEDULE", "gpipe"),
        wire_codec=os.environ.get("REPRO_PIPELINE_WIRE_CODEC", "none"),
    )
    params_shapes = jax.eval_shape(
        lambda k: init_pipeline_params(k, cfg.model, spec), jax.random.key(0))
    from repro.common import tree_map_with_path_str
    from jax.sharding import PartitionSpec as P

    def spec_for(path, leaf):
        if path.startswith("stages/"):
            return P("model")
        if "embed" in path:
            return P(ma.model, ma.data if ma.fsdp else None)
        return P()

    p_specs = tree_map_with_path_str(spec_for, params_shapes)
    params_in = _shape_structs(params_shapes, p_specs, mesh)
    batch_shapes = model.input_specs(shape)
    b_specs = batch_specs(model, shape, ma)
    batch_in = _shape_structs(batch_shapes, b_specs, mesh)

    fused = os.environ.get("REPRO_PIPELINE_FUSED", "1") == "1"

    if spec.schedule == "1f1b" or fused:
        # the dispatcher pairs each schedule with its grad path (autodiff
        # for GPipe, the explicit-backward slot loop for 1F1B)
        def step(params, batch):
            _, grads = pipeline_loss_and_grads(params, batch, cfg.model,
                                               spec, mesh,
                                               batch_axes=ma.batch)
            return grads
    else:
        def step(params, batch):
            return jax.grad(lambda p, b: pipeline_loss(
                p, b, cfg.model, spec, mesh, batch_axes=ma.batch))(
                    params, batch)

    lowered = jax.jit(step).lower(params_in, batch_in)
    return lowered.compile(), {
        "pipeline": {"n_stages": spec.n_stages,
                     "n_microbatches": spec.n_microbatches,
                     "compress": spec.compress,
                     "bottleneck_dim": spec.bottleneck_dim,
                     "schedule": spec.schedule,
                     "wire_codec": spec.wire_codec}}


def run_outer_merge(arch_id: str) -> dict:
    """Lower + compile the DiLoCo outer merge (paper full-sync stage) on the

    multi-pod mesh: butterfly-redundant reduce-scatter + agreement check +
    all-gather of the parameter delta over the ``pod`` axis, plus the outer
    Nesterov step.  Its collective bytes are the per-sync DCN cost that the
    paper's App. A stability analysis trades against gamma; recorded in
    EXPERIMENTS.md §Dry-run.
    """
    from repro.core import diloco
    cfg = configs.get(arch_id)
    mesh = make_production_mesh(multi_pod=True)
    chips = int(np.prod(list(mesh.shape.values())))
    model = build_model(cfg)
    ma = make_mesh_axes(mesh, cfg.model, cfg.parallel)
    params_shapes = jax.eval_shape(model.init, jax.random.key(0))
    p_specs = param_specs(params_shapes, ma)
    params_in = _shape_structs(params_shapes, p_specs, mesh)
    outer_shapes = jax.eval_shape(diloco.outer_init, params_shapes)
    # anchor/momentum shard like params (momentum is fp32)
    outer_specs = diloco.OuterState(
        anchor=p_specs, momentum=p_specs,
        outer_step=jax.sharding.PartitionSpec())
    outer_in = _shape_structs(outer_shapes, outer_specs, mesh)

    def step(params, outer):
        return diloco.outer_merge_step(params, outer, mesh, axis="pod",
                                       param_specs=p_specs)

    t0 = time.time()
    with mesh:
        compiled = jax.jit(step).lower(params_in, outer_in).compile()
    from repro.launch import hlo_cost
    hc = hlo_cost.analyze_module(compiled.as_text())
    rec = {
        "arch": arch_id, "kind": "diloco_outer_merge", "mesh": "multi_pod",
        "status": "ok", "chips": chips,
        "compile_seconds": round(time.time() - t0, 1),
        "device_collective_bytes": float(hc.collective_bytes),
        "collectives": {"bytes": dict(hc.coll_by_kind),
                        "count": dict(hc.coll_count)},
        "t_collective_dcn": float(hc.collective_bytes) / 50e9,
    }
    print(f"[{arch_id} x outer_merge x multi_pod] compiled in "
          f"{rec['compile_seconds']}s | coll {hc.collective_bytes/1e9:.2f} "
          f"GB/device | t_dcn {rec['t_collective_dcn']:.3f}s")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every (arch x applicable shape) on both meshes")
    ap.add_argument("--strategy", default="tensor",
                    choices=["tensor", "pipeline", "outer-merge"])
    ap.add_argument("--out", type=str, default=None)
    args = ap.parse_args()

    cells: list[tuple[str, str, bool]] = []
    if args.all:
        for arch in configs.all_arch_ids():
            cfg = configs.get(arch)
            for shape in applicable_shapes(cfg.model):
                cells.append((arch, shape.name, False))
                cells.append((arch, shape.name, True))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        meshes = [False, True] if args.both_meshes else [args.multi_pod]
        cells = [(args.arch, args.shape, mp) for mp in meshes]

    results = []
    for arch, shape, mp in cells:
        try:
            if args.strategy == "outer-merge":
                results.append(run_outer_merge(arch))
                continue
            results.append(run_cell(arch, shape, mp, args.strategy))
        except Exception as e:  # noqa: BLE001 — record per-cell failures
            traceback.print_exc()
            results.append({"arch": arch, "shape": shape,
                            "mesh": "multi_pod" if mp else "single_pod",
                            "status": "error", "error": repr(e)})
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1, default=str)
        print(f"wrote {len(results)} records to {args.out}")
    n_err = sum(1 for r in results if r.get("status") == "error")
    print(f"dry-run: {len(results)} cells, {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
