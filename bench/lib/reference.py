"""Plain float32 reference of the model stages the benchmark trains: the
parts every model family shares.

Written from the published architecture and the configuration file alone:
it imports nothing of the program under test.  A stage is the family's
layers (``bench/models/<family>.py`` ``blocks``) between IOTA's entry and
exit, which every family has: the embedding or the bottleneck decode in,
the bottleneck encode or the head out.  It works on parameter trees
of the program's layout (leaf names and shapes), which the benchmark fills
itself from the seed (``make_weights``), so both sides start from the same
numbers without the reference reading anything the program made.

Every matrix product runs at ``Precision.HIGHEST`` in float32.  ``mode="fp8"``
is the control, the step below the bfloat16 the configuration states, as
fp8 training runs it: every product's operands are rounded to float8_e4m3
under a per-tensor scale, and in the backward the incoming gradient is
rounded to float8_e5m2 before the products that carry it back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import models

HIGHEST = jax.lax.Precision.HIGHEST
E4M3 = jnp.float8_e4m3fn          # fp8 values and weights
E5M2 = jnp.float8_e5m2            # fp8 gradients


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------


def leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def weight_maker(shapes: list, init_leaf, shardings=None):
    """``make(seed)``: every stage's weights, of the trees ``shapes``
    (ShapeDtypeStructs), made on the device in one jitted call from
    ``seed``: leaf i of stage s draws from the key (seed, s, i), by the
    family's ``init_leaf``.  ``shardings``, where given, is a tree of
    shardings per stage tree: the weights are made in place there.
    ``make.trees(key)`` is the same from ``key``, to call inside another
    jitted function."""
    flat = [jax.tree_util.tree_flatten_with_path(t) for t in shapes]
    plan = [[(leaf_name(p), tuple(x.shape), x.dtype) for p, x in leaves]
            for leaves, _ in flat]

    def draw(key):
        out = []
        for stage, leaves in enumerate(plan):
            k = jax.random.fold_in(key, stage)
            out.append([init_leaf(jax.random.fold_in(k, i), n, shp, dt)
                        for i, (n, shp, dt) in enumerate(leaves)])
        return out

    draw = jax.jit(draw) if shardings is None else jax.jit(
        draw, out_shardings=[jax.tree.leaves(s) for s in shardings])

    def trees(key) -> list:
        return [jax.tree_util.tree_unflatten(treedef, leaves)
                for (_, treedef), leaves in zip(flat, draw(key))]

    def make(seed: int) -> list:
        return trees(seed_key(seed))

    make.trees = trees
    return make


def seed_key(seed: int):
    """The key the weights of ``seed`` are drawn from."""
    return jax.random.key(seed % 2**32)


def make_weights(seed: int, shapes: list, init_leaf, shardings=None) -> list:
    """``weight_maker``'s weights for ``seed``."""
    return weight_maker(shapes, init_leaf, shardings)(seed)


# ---------------------------------------------------------------------------
# products
# ---------------------------------------------------------------------------


def _round(x, dtype):
    """Round to ``dtype`` under a per-tensor scale that maps the largest
    magnitude to the format's largest value."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _product(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum_fp8(spec, a, b):
    return _product(spec, _round(a, E4M3), _round(b, E4M3))


def _einsum_fp8_fwd(spec, a, b):
    qa, qb = _round(a, E4M3), _round(b, E4M3)
    return _product(spec, qa, qb), (qa, qb)


def _einsum_fp8_bwd(spec, saved, g):
    _, vjp = jax.vjp(functools.partial(_product, spec), *saved)
    return vjp(_round(g, E5M2))


_einsum_fp8.defvjp(_einsum_fp8_fwd, _einsum_fp8_bwd)


def einsum(spec: str, a, b, mode: str):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if mode == "fp8":
        return _einsum_fp8(spec, a, b)
    return _product(spec, a, b)


# ---------------------------------------------------------------------------
# the norm every family shares
# ---------------------------------------------------------------------------


def rmsnorm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gamma


# ---------------------------------------------------------------------------
# stages: tokens -> code -> ... -> loss
# ---------------------------------------------------------------------------


def stage_in(p, x_in, m, mode):
    """Stage entry: the token embedding, or the bottleneck decode
    alpha * (z @ w_up)."""
    if "embeds" in p:
        return jnp.take(p["embeds"]["embed"], x_in, axis=0).astype(jnp.float32)
    return p["alpha_dec"] * einsum("bsc,cd->bsd", x_in, p["w_up"], mode)


def stage_out(p, x, m, mode):
    """Stage exit: the bottleneck encode rmsnorm(x) @ w_down, or the
    logits over the real vocabulary."""
    if "w_down" in p:
        return einsum("bsd,dc->bsc", rmsnorm(x, p["enc_norm"], m["norm_eps"]),
                      p["w_down"], mode)
    x = rmsnorm(x, p["final_norm"], m["norm_eps"])
    table = p["unembed"][: m["vocab_size"]]
    return einsum("bsd,vd->bsv", x, table, mode)


def stage(p, x_in, m, mode, family: str, first: int = 0):
    """One stage of the family ``family`` (``bench.models.get``), whose
    first layer is the model's layer ``first``."""
    blocks = models.get(family).blocks
    return stage_out(p, blocks(p["blocks"], stage_in(p, x_in, m, mode), m,
                               mode, first), m, mode)


def token_loss(logits, labels, z_loss: float = 0.0):
    """Mean next-token cross entropy, with ``z_loss`` x logsumexp^2 added
    to each token's where the objective has that penalty."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - true
    if z_loss:
        nll = nll + z_loss * jnp.square(lse)
    return jnp.mean(nll)


def loss_fn(stage_params: list, tokens, labels, m, mode, family: str):
    """Mean next-token cross entropy through every stage in order."""
    x, first = tokens, 0
    for p in stage_params:
        x = stage(p, x, m, mode, family, first)
        first += jax.tree.leaves(p["blocks"])[0].shape[0]
    return token_loss(x, labels)


# ---------------------------------------------------------------------------
# AdamW with linear warm-up then cosine decay (decoupled weight decay)
# ---------------------------------------------------------------------------


def lr_at(step, opt):
    """Learning rate of update number ``step`` (1-based)."""
    step = jnp.asarray(step, jnp.float32)
    lr, warm, total = opt["lr"], opt["warmup_steps"], opt["total_steps"]
    floor = opt["min_lr_ratio"]
    prog = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return jnp.where(step < warm, lr * step / max(warm, 1), lr * cos)


def adamw_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"mu": zeros, "nu": jax.tree.map(jnp.zeros_like, params),
            "step": jnp.zeros((), jnp.int32)}


def adamw_update(grads, state, params, opt):
    step = state["step"] + 1
    lr = lr_at(step, opt)
    b1, b2, eps, wd = opt["beta1"], opt["beta2"], opt["eps"], \
        opt["weight_decay"]
    t = step.astype(jnp.float32)
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["nu"],
                      grads)
    new = jax.tree.map(
        lambda p, m, v: p - lr * ((m / (1 - b1 ** t))
                                  / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
                                  + wd * p),
        params, mu, nu)
    return new, {"mu": mu, "nu": nu, "step": step}


@functools.partial(jax.jit, static_argnames=("m", "opt", "mode", "trained",
                                             "family"))
def train_tick(params: tuple, states: tuple, tokens, labels, *, m, opt, mode,
               trained: tuple, family: str):
    """One pathway through the stages: loss, gradients, then an AdamW update
    of each stage whose flag in ``trained`` is set.  ``m`` and ``opt`` are
    hashable (tuple-of-items) views of the configuration."""
    m, opt = dict(m), dict(opt)
    loss, grads = jax.value_and_grad(
        lambda ps: loss_fn(list(ps), tokens, labels, m, mode, family))(
            tuple(params))
    out_p, out_s = [], []
    for p, s, g, on in zip(params, states, grads, trained):
        if on:
            p, s = adamw_update(g, s, p, opt)
        out_p.append(p)
        out_s.append(s)
    return loss, tuple(out_p), tuple(out_s)


# ---------------------------------------------------------------------------
# the compressed sharing and the outer step
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("block",))
def int8_roundtrip(vec, block: int):
    """Symmetric per-block int8 over a flat float32 vector: scale =
    amax / 127 (1 where amax is 0), code = round(x / scale) clipped to
    +-127, value = code * scale."""
    n = vec.shape[0]
    x = jnp.pad(vec.astype(jnp.float32), (0, (-n) % block)).reshape(-1, block)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    return (q * scale).reshape(-1)[:n]


def outer_nesterov(anchor, avg, lr: float, momentum: float):
    """First outer step from zero momentum: d = anchor - avg,
    m = d, anchor - lr * (d + momentum * m)."""
    d = anchor - avg
    return anchor - lr * (d + momentum * d)
