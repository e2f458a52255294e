"""Bring-up check: drive the swarm's main path once on one TPU chip.

    python chip_smoke.py                # kernels, swarm, serve on one chip
    python chip_smoke.py --four-chips   # the on-mesh pipeline on a 2x2 host

Everything runs in this one process: a chip belongs to one process, so no
phase spawns a child that would reach for it.  Weights and data are random,
made from a seed.  Phases, in order:

  kernels  every main-path Pallas kernel against ``kernels/ref.py`` on the
           chip (reference at ``default_matmul_precision("highest")``), at
           the shapes the next two phases use.
  swarm    one in-process swarm epoch after a warm-up epoch
           (``Swarm.create`` + ``run_epoch``): the paper's
           iota-bottleneck-1.5b at its published widths, cut in depth and
           vocabulary only, 2 stages x 2 miners, int8 sharing, the sharded
           butterfly sync and one validator.
  serve    the whole model (16 layers, full vocabulary) served by
           ``serve_swarm`` over 2 stages and 4 lanes, token for token
           against the sequential ``swarm_generate`` oracle.

``--four-chips`` runs only the cross-chip path: ``launch.train`` with the
1f1b pipeline over 4 stages at full width and depth, then the gpipe golden
oracle from the same seed, loss for loss.

There is no CPU fallback: where JAX finds no TPU the script exits non-zero
before any work and prints no result.  Each phase prints its wall time,
compile time and device memory on its own line (bring-up facts, not
benchmark numbers); the last line is the one JSON result.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import io
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "iota-bottleneck-1.5b"
SEED = 0


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The shapes every phase runs at.  ``FULL`` is the chip run; ``TINY``
    is a CPU rehearsal at smoke widths, reachable only from tests."""
    smoke_widths: bool
    swarm_layers: int           # one layer per stage
    swarm_vocab: int
    bottleneck_dim: int
    batch: int
    seq_len: int
    serve_requests: int
    serve_lanes: int
    prompt_len: int
    max_new: int


FULL = Sizes(smoke_widths=False, swarm_layers=2, swarm_vocab=128256 // 8,
             bottleneck_dim=32, batch=4, seq_len=1024, serve_requests=8,
             serve_lanes=4, prompt_len=512, max_new=32)
TINY = Sizes(smoke_widths=True, swarm_layers=2, swarm_vocab=512,
             bottleneck_dim=8, batch=4, seq_len=32, serve_requests=3,
             serve_lanes=2, prompt_len=16, max_new=4)

# bf16 operands and a bf16 result: the kernel and the reference round the
# same f32 accumulation to bf16 in a different order, which moves an output
# by up to ~2 bf16 ulps (2**-7 relative) — tests/test_kernels.py's bf16 bound
BF16_TOL = dict(rtol=3e-2, atol=3e-2)
# the masked mean is exact f32 arithmetic on both sides (a sum of M values
# then one division), so only the summation order can differ
MERGE_TOL = dict(rtol=1e-5, atol=1e-6)


class CheckFailed(Exception):
    """A bring-up check did not hold; the script exits non-zero."""


def check(ok, what) -> None:
    """Raise unless ``ok`` (an explicit raise: ``assert`` vanishes under
    ``python -O``, and a check must never be skipped)."""
    if not ok:
        raise CheckFailed(what)


def log(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events (the ``/jax/core/compile/`` durations)."""

    def __init__(self):
        self.seconds = 0.0

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def memory() -> dict:
    """bytes_in_use / peak_bytes_in_use per device (peak is process-wide
    so far); None where the backend keeps no statistics."""
    out = {}
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        out[d.id] = {k: st.get(k) for k in ("bytes_in_use",
                                            "peak_bytes_in_use")}
    return out


def live_bytes() -> int:
    return sum(a.nbytes for a in jax.live_arrays())


def lowers_to_kernel(fn, *args, **static) -> bool:
    """True when the jitted program holds a Pallas kernel (a compiled
    kernel lowers to ``tpu_custom_call``; the reference lowers to plain
    HLO).  Lowering needs only shapes and compiles nothing."""
    return "tpu_custom_call" in fn.lower(*args, **static).as_text()


def run_phase(name: str, fn, sizes: Sizes) -> None:
    t0 = time.perf_counter()
    with CompileClock() as clock:
        facts = fn(sizes)
    wall = time.perf_counter() - t0
    mem_end = memory()
    gc.collect()
    held = live_bytes()
    log(phase=name, status="pass", wall_s=wall, compile_s=clock.seconds,
        memory=mem_end, live_bytes_after_release=held, **facts)
    # every phase frees what it made before the next one starts
    check(held < 512 * 2**20, f"phase {name} left {held} bytes on device")


def model_cfg(sizes: Sizes):
    from repro import configs
    arch = configs.get(ARCH)
    if sizes.smoke_widths:
        arch = configs.smoke_variant(arch)
    return arch.model


def swarm_model_cfg(sizes: Sizes):
    return dataclasses.replace(model_cfg(sizes), n_layers=sizes.swarm_layers,
                               vocab_size=sizes.swarm_vocab)


def stage_vector_len(sizes: Sizes) -> int:
    """Length of one stage's flattened f32 weight vector — what the int8
    share codec and the butterfly merge see in the swarm phase."""
    from repro.runtime import stage_model as sm
    spec = sm.SwarmModelSpec(swarm_model_cfg(sizes), 2, True,
                             sizes.bottleneck_dim)
    shapes = jax.eval_shape(lambda k: sm.init_stage_params(k, spec, 0),
                            jax.random.key(SEED))
    return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------


def _allclose(name, got, want, **tol) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, (name, got.shape, want.shape))
    check(np.all(np.isfinite(got)), f"{name}: non-finite kernel output")
    np.testing.assert_allclose(got, want, err_msg=name, **tol)
    return float(np.max(np.abs(got - want)))


def _check_codes(name, q, s, q_ref, s_ref) -> dict:
    """int8 codes: scales agree to f32 rounding; a code may differ by one
    where x / scale sits on a rounding tie that the last bit of the scale
    decides."""
    _allclose(name + "/scales", s, s_ref, rtol=1e-6, atol=0)
    dq = np.abs(np.asarray(q, np.int32) - np.asarray(q_ref, np.int32))
    check(dq.max() <= 1, (name, int(dq.max())))
    frac = float(np.mean(dq > 0))
    check(frac < 1e-4, (name, frac))
    return {"codes_off_by_one": frac}


def phase_kernels(sizes: Sizes) -> dict:
    from repro.kernels import ops, ref
    cfg = model_cfg(sizes)
    H, KH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    B, S, d, db = sizes.batch, sizes.seq_len, cfg.d_model, sizes.bottleneck_dim
    keys = iter(jax.random.split(jax.random.key(SEED), 16))

    def normal(shape, dtype=jnp.float32, scale=1.0):
        return (jax.random.normal(next(keys), shape) * scale).astype(dtype)

    errs, kernels_in_programs = {}, {}
    hi = jax.default_matmul_precision("highest")

    def compare(name, kernel_fn, ref_fn, args, **tol):
        got = kernel_fn(*args)
        with hi:
            want = ref_fn(*args)
        errs[name] = _allclose(name, got, want, **tol)
        kernels_in_programs[name] = lowers_to_kernel(jax.jit(kernel_fn),
                                                     *args)

    # flash attention: the swarm's training shape
    q = normal((B, S, H, hd), jnp.bfloat16)
    k = normal((B, S, KH, hd), jnp.bfloat16)
    v = normal((B, S, KH, hd), jnp.bfloat16)
    compare("flash_attention", lambda q, k, v: ops.flash_attention(q, k, v),
            lambda q, k, v: ref.attention(q, k, v), (q, k, v), **BF16_TOL)
    del q, k, v

    # cached attention: the serve phase's decode (one new row per lane,
    # the lanes' caches filled to different lengths) and prefill (the
    # whole prompt into an empty cache)
    max_len = sizes.prompt_len + sizes.max_new
    L = sizes.serve_lanes
    lens = jnp.asarray(np.linspace(sizes.prompt_len + 1, max_len, L),
                       jnp.int32)
    kc = normal((L, max_len, KH, hd), jnp.bfloat16)
    vc = normal((L, max_len, KH, hd), jnp.bfloat16)
    qd = normal((L, 1, H, hd), jnp.bfloat16)

    def cached(q, k, v, off, n):
        return ops.flash_attention(q, k, v, causal=True, q_offset=off,
                                   kv_len=n)

    def cached_ref(q, k, v, off, n):
        return ref.attention(q, k, v, causal=True, q_offset=off, kv_len=n)

    compare("decode_attention/decode", cached, cached_ref,
            (qd, kc, vc, jnp.int32(max_len - 1), lens), **BF16_TOL)
    qp = normal((1, sizes.prompt_len, H, hd), jnp.bfloat16)
    n_p = jnp.asarray([sizes.prompt_len], jnp.int32)
    compare("decode_attention/prefill", cached, cached_ref,
            (qp, kc[:1], vc[:1], jnp.int32(0), n_p), **BF16_TOL)
    del kc, vc, qd, qp

    # int8 share codec on one stage's whole weight vector
    n = -(-stage_vector_len(sizes) // 256) * 256
    w = normal((n,), scale=0.02)
    qv, sv = ops.quantize_int8(w)
    qr, sr = ref.quantize_int8(w)
    codec = _check_codes("quantize_int8", qv, sv, qr, sr)
    errs["dequantize_int8"] = _allclose(
        "dequantize_int8", ops.dequantize_int8(qr, sr),
        ref.dequantize_int8(qr, sr), rtol=1e-6, atol=0)
    kernels_in_programs["quantize_int8"] = lowers_to_kernel(
        jax.jit(ops.quantize_int8), w)
    del w, qv, sv, qr, sr

    # the int8 pipeline wire on a (B, S, d_b) bottleneck code
    z = normal((B, S, db))
    qw, sw = ops.wire_encode(z)
    blk = ref.wire_code_block(z.size, db)
    qwr, swr = ref.quantize_int8(z.reshape(-1), block=blk)
    wire = _check_codes("quantize_wire", qw.reshape(-1), sw, qwr, swr)
    kernels_in_programs["quantize_wire"] = lowers_to_kernel(
        jax.jit(ops.wire_encode), z)
    del z, qw, sw, qwr, swr

    # butterfly merge: the sharded sync's one shard, two miners' copies
    shards = normal((2, n), scale=0.02)
    valid = jnp.asarray([True, True])
    compare("shard_merge", ops.shard_merge, ref.shard_merge,
            (shards, valid), **MERGE_TOL)
    del shards

    # bottleneck codecs at a stage boundary
    x = normal((B, S, d), jnp.bfloat16)
    gamma = 1.0 + normal((d,), scale=0.1)
    w_down = normal((d, db), scale=1 / math.sqrt(d))
    compare("bottleneck_encode",
            lambda x, g, w: ops.bottleneck_encode(x, g, w),
            lambda x, g, w: ref.bottleneck_encode(x, g, w),
            (x, gamma, w_down), **BF16_TOL)
    zc = normal((B, S, db), jnp.bfloat16)
    w_up = normal((db, d), scale=1 / math.sqrt(db))
    alpha = jnp.float32(0.5)
    compare("bottleneck_decode_gated",
            lambda z, w, a: ops.bottleneck_decode_gated(z, w, a),
            lambda z, w, a: ref.bottleneck_decode_gated(z, w, a),
            (zc, w_up, alpha), **BF16_TOL)
    del x, gamma, w_down, zc, w_up

    return {"max_abs_err": errs, "int8_codes": {"share": codec, "wire": wire},
            "stage_vector_len": n,
            "kernels_in_programs": require_kernels(kernels_in_programs,
                                                   sizes)}


def require_kernels(found: dict, sizes: Sizes) -> dict:
    """On the chip every listed program must hold its kernel; the CPU
    rehearsal runs the kernel bodies in interpret mode, which lowers to
    plain HLO, so there the facts are reported and not required."""
    if not sizes.smoke_widths:
        missing = [k for k, ok in found.items() if not ok]
        check(not missing, f"no tpu_custom_call in {missing}")
    return found


# ---------------------------------------------------------------------------
# phase: swarm
# ---------------------------------------------------------------------------


def phase_swarm(sizes: Sizes) -> dict:
    from repro.api import Swarm, SwarmConfig
    from repro.runtime import stage_model as sm

    cfg = swarm_model_cfg(sizes)
    config = SwarmConfig(
        n_stages=2, miners_per_stage=2, validators=1,
        batch_size=sizes.batch, seq_len=sizes.seq_len,
        bottleneck_dim=sizes.bottleneck_dim, share_codec="int8",
        sync_mode="sharded",
        # 4 ticks route each batch through one random miner per stage; at
        # this seed every miner trains at least once, so both stages merge
        inner_steps=4, b_min=1, quorum_frac=0.5, seed=SEED)
    swarm = Swarm.create(cfg, config)
    params0 = [swarm.miners[0].params, swarm.stage_miners(1)[0].params]
    tokens = jax.ShapeDtypeStruct((sizes.batch, sizes.seq_len), jnp.int32)
    code = jax.ShapeDtypeStruct((sizes.batch, sizes.seq_len,
                                 sizes.bottleneck_dim), jnp.bfloat16)
    kernels = require_kernels({
        "stage_forward/first": lowers_to_kernel(
            sm.stage_forward, params0[0], tokens, spec=swarm.spec,
            role="first"),
        "stage_forward/last": lowers_to_kernel(
            sm.stage_forward, params0[1], code, spec=swarm.spec,
            role="last"),
    }, sizes)
    del params0

    epochs = []
    for _ in range(2):                    # warm-up epoch, then the epoch
        t0 = time.perf_counter()
        with CompileClock() as clock:
            stats = swarm.run_epoch()
        epochs.append({"epoch": stats.epoch, "wall_s":
                       time.perf_counter() - t0, "compile_s": clock.seconds,
                       "mean_loss": stats.mean_loss,
                       "merged_stages": stats.merged_stages,
                       "batches": stats.batches})
    check(math.isfinite(stats.mean_loss), stats.mean_loss)
    check(stats.merged_stages == 2, stats.merged_stages)
    check(stats.validation, "no validator result")
    res = stats.validation[0]
    check(res.checked > 0 and res.passed == res.checked, res)
    n_params = [sum(x.size for x in jax.tree.leaves(a))
                for a in swarm.anchors]
    del swarm, stats
    return {"model": {"arch": ARCH, "d_model": cfg.d_model,
                      "d_ff": cfg.d_ff, "heads": [cfg.n_heads,
                                                  cfg.n_kv_heads],
                      "head_dim": cfg.head_dim,
                      "bottleneck_dim": sizes.bottleneck_dim},
            "reduced": {"n_layers": [16, cfg.n_layers],
                        "vocab_size": [128256, cfg.vocab_size]}
            if not sizes.smoke_widths else "smoke widths (CPU rehearsal)",
            "params_per_stage": n_params, "epochs": epochs,
            "validation": {"miner": res.miner_uid, "checked": res.checked,
                           "passed": res.passed,
                           "min_cosine": res.min_cosine},
            "kernels_in_programs": kernels}


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------


def phase_serve(sizes: Sizes) -> dict:
    from repro.api.phases import ServeRequest
    from repro.launch.serve import serve_swarm, swarm_generate
    from repro.runtime import stage_model as sm

    cfg = model_cfg(sizes)
    spec = sm.SwarmModelSpec(cfg, 2, True, sizes.bottleneck_dim)
    max_len = sizes.prompt_len + sizes.max_new
    last = jax.eval_shape(lambda: sm.serve_stage_params(spec, SEED, 1))
    cache = jax.eval_shape(lambda: sm.init_stage_cache(spec, 1, 1, max_len))
    kernels = require_kernels({
        f"stage_decode_step/{name}": lowers_to_kernel(
            sm.stage_decode_step, last,
            jax.ShapeDtypeStruct((1, rows, sizes.bottleneck_dim),
                                 jnp.bfloat16),
            cache, spec=spec, role="last")
        for name, rows in (("prefill", sizes.prompt_len), ("decode", 1))
    }, sizes)

    prompts = np.random.RandomState(SEED).randint(
        0, cfg.vocab_size, (sizes.serve_requests, sizes.prompt_len))
    requests = [ServeRequest(req=i, prompt=prompts[i],
                             max_new=sizes.max_new)
                for i in range(sizes.serve_requests)]
    t0 = time.perf_counter()
    records = serve_swarm(spec, requests, n_lanes=sizes.serve_lanes,
                          max_len=max_len, transport="inprocess", seed=SEED)
    t_serve = time.perf_counter() - t0
    gc.collect()                  # the servers' weights go before the oracle
    t0 = time.perf_counter()
    oracle = swarm_generate(spec, SEED, requests)
    t_oracle = time.perf_counter() - t0
    mismatched = [r for r in records if records[r].tokens != oracle[r]]
    check(not mismatched,
          f"serve_swarm and the swarm_generate oracle disagree on requests "
          f"{mismatched}")
    n_tok = sum(len(r.tokens) for r in records.values())
    check(n_tok == sizes.serve_requests * sizes.max_new, n_tok)
    return {"model": {"arch": ARCH, "n_layers": cfg.n_layers,
                      "vocab_size": cfg.vocab_size, "stages": 2,
                      "lanes": sizes.serve_lanes},
            "requests": sizes.serve_requests,
            "prompt_len": sizes.prompt_len, "tokens": n_tok,
            "token_parity_with_oracle": True,
            "serve_wall_s": t_serve, "oracle_wall_s": t_oracle,
            "kernels_in_programs": kernels}


# ---------------------------------------------------------------------------
# --four-chips: the on-mesh pipeline against its gpipe golden oracle
# ---------------------------------------------------------------------------

# 1f1b and gpipe run the same per-microbatch stage math through the same
# boundary codecs and differ only in the order of float reductions.  From
# the same parameters tests/test_pipeline_schedules.py holds their losses
# to 5e-6 and their gradients to 5e-5, relative: step 1 is that case (the
# gradients are compared through their norm).  Each SGD step then moves
# the two runs' parameters apart by lr times their gradient gap, so later
# losses are held to the gradient bound, relative to the loss.
SAME_PARAMS_LOSS_TOL = 5e-6
GRAD_REL_TOL = 5e-5


def run_train(schedule: str, sizes: Sizes) -> list:
    from repro.launch import train
    argv = ["--arch", ARCH, "--strategy", "pipeline",
            "--pipeline-stages", "4", "--pipeline-schedule", schedule,
            "--wire-codec", "none",
            "--bottleneck-dim", str(sizes.bottleneck_dim),
            "--batch-size", "8", "--seq-len", str(sizes.seq_len),
            "--pipeline-microbatches", "8", "--steps", "3",
            "--log-every", "1", "--seed", str(SEED)]
    if sizes.smoke_widths:
        argv.append("--smoke")
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        train.main(argv)
    print(out.getvalue(), end="", flush=True)
    return [json.loads(line) for line in out.getvalue().splitlines()
            if line.startswith("{")]


def phase_pipeline(sizes: Sizes) -> dict:
    check(jax.device_count() == 4, jax.device_count())
    runs = {}
    for schedule in ("1f1b", "gpipe"):
        records = run_train(schedule, sizes)
        stats = records[0]
        losses = [r["loss"] for r in records[1:] if "loss" in r]
        check(len(losses) == 3 and all(map(math.isfinite, losses)), losses)
        # the stage blocks split over the 4 chips and the rest is
        # replicated, so every chip holds the same bytes of weights
        per_chip = stats["param_bytes_per_device"]
        check(len(per_chip) == 4 and len(set(per_chip)) == 1, per_chip)
        runs[schedule] = {"losses": losses,
                          "grad_norm_step1": records[1]["grad_norm"],
                          "param_bytes_per_chip": per_chip,
                          "memory": memory()}
        gc.collect()
    a, b = runs["1f1b"], runs["gpipe"]
    gaps = [abs(x - y) for x, y in zip(a["losses"], b["losses"])]
    grad_gap = (abs(a["grad_norm_step1"] - b["grad_norm_step1"])
                / b["grad_norm_step1"])
    check(gaps[0] < SAME_PARAMS_LOSS_TOL, ("step 1 loss", gaps[0]))
    check(grad_gap < GRAD_REL_TOL, ("step 1 grad norm", grad_gap))
    later = [g / abs(y) for g, y in zip(gaps[1:], b["losses"][1:])]
    check(max(later) < GRAD_REL_TOL, ("later losses, relative", later))
    return {"runs": runs, "loss_gaps_1f1b_vs_gpipe": gaps,
            "grad_norm_gap_step1_relative": grad_gap,
            "later_loss_gaps_relative": later,
            "tolerances": {"step1_loss": SAME_PARAMS_LOSS_TOL,
                           "grad_and_later_loss_relative": GRAD_REL_TOL}}


# ---------------------------------------------------------------------------


def main(argv=None, sizes: Sizes | None = None) -> int:
    """Returns the exit code.  ``sizes`` other than ``FULL`` is the CPU
    rehearsal: the phases run first and the platform check refuses last."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-stage on-mesh pipeline and its "
                         "gpipe comparison, on a 4-chip host")
    args = ap.parse_args(argv)
    rehearsal = sizes is not None
    sizes = sizes or FULL
    dev = jax.devices()[0]
    if not rehearsal and dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); "
              f"this check runs on the chip only", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import use_compile_cache
    log(device={"platform": dev.platform, "kind": dev.device_kind,
                "count": jax.device_count()},
        compile_cache=use_compile_cache())
    phases = [("pipeline", phase_pipeline)] if args.four_chips else [
        ("kernels", phase_kernels), ("swarm", phase_swarm),
        ("serve", phase_serve)]
    for name, fn in phases:
        run_phase(name, fn, sizes)

    if dev.platform != "tpu":
        print("chip_smoke: every phase passed, but not on a TPU",
              file=sys.stderr)
        return 2
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
