"""Shared utilities: pytree helpers, dtype policy, deterministic RNG folding.

Everything in this module is dependency-free (jax + numpy only) and safe to
import from any layer of the stack.
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
from typing import Any, Callable, Iterable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

# ---------------------------------------------------------------------------
# Host spans
# ---------------------------------------------------------------------------

SPAN_PREFIX = "iota."


def span(name: str, **counts) -> jax.profiler.TraceAnnotation:
    """Host span ``iota.<name>`` in the JAX profiler's trace.

    It records only while a trace is being taken, on the clock of the
    device's op events, so an idle stretch of the chip can be put down to
    the host call around it.  Spans wrap host calls only, never traced
    code, and never wait on the device.  ``counts`` (``bytes=``) are the
    span's metadata: pass only values already in hand when it opens."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **counts)

# ---------------------------------------------------------------------------
# Pytree helpers
# ---------------------------------------------------------------------------


def tree_size(tree: PyTree) -> int:
    """Total number of elements across all leaves."""
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def tree_bytes(tree: PyTree) -> int:
    """Total bytes across all leaves (respects per-leaf dtype)."""
    return sum(
        int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
        for x in jax.tree_util.tree_leaves(tree)
    )


def tree_zeros_like(tree: PyTree) -> PyTree:
    return jax.tree.map(jnp.zeros_like, tree)


def tree_add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.subtract, a, b)


def tree_scale(tree: PyTree, s) -> PyTree:
    return jax.tree.map(lambda x: x * s, tree)


def tree_axpy(alpha, x: PyTree, y: PyTree) -> PyTree:
    """alpha * x + y, leaf-wise."""
    return jax.tree.map(lambda a, b: alpha * a + b, x, y)


def tree_dot(a: PyTree, b: PyTree) -> jax.Array:
    leaves = jax.tree.map(lambda x, y: jnp.vdot(x, y), a, b)
    return jax.tree_util.tree_reduce(jnp.add, leaves)


def tree_norm(tree: PyTree) -> jax.Array:
    return jnp.sqrt(tree_dot(tree, tree))


def tree_cast(tree: PyTree, dtype) -> PyTree:
    return jax.tree.map(lambda x: x.astype(dtype), tree)


def global_norm(tree: PyTree) -> jax.Array:
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
            for x in jax.tree_util.tree_leaves(tree))
    )


def tree_flatten_to_vector(tree: PyTree) -> tuple[jax.Array, Callable[[jax.Array], PyTree]]:
    """Flatten a pytree into one fp32 vector plus an unflatten closure.

    Used by the butterfly all-reduce, which shards the *flattened* parameter
    space into |P| = N(N-1)/2 near-equal byte ranges (paper §5.1).
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = [l.shape for l in leaves]
    dtypes = [l.dtype for l in leaves]
    sizes = [int(np.prod(s)) for s in shapes]
    vec = jnp.concatenate([l.astype(jnp.float32).reshape(-1) for l in leaves]) \
        if leaves else jnp.zeros((0,), jnp.float32)

    def unflatten(v: jax.Array) -> PyTree:
        out, off = [], 0
        for shape, dtype, size in zip(shapes, dtypes, sizes):
            out.append(v[off:off + size].reshape(shape).astype(dtype))
            off += size
        return jax.tree_util.tree_unflatten(treedef, out)

    return vec, unflatten


def tree_paths(tree: PyTree) -> list[str]:
    """'/'-joined string path for every leaf, in tree_flatten order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, _leaf in flat:
        out.append("/".join(_path_str(p) for p in path))
    return out


def _path_str(p) -> str:
    if isinstance(p, jax.tree_util.DictKey):
        return str(p.key)
    if isinstance(p, jax.tree_util.SequenceKey):
        return str(p.idx)
    if isinstance(p, jax.tree_util.GetAttrKey):
        return str(p.name)
    return str(p)


def tree_map_with_path_str(fn: Callable[[str, Any], Any], tree: PyTree) -> PyTree:
    """tree_map where fn also receives the '/'-joined path string."""
    def wrapper(path, leaf):
        return fn("/".join(_path_str(p) for p in path), leaf)
    return jax.tree_util.tree_map_with_path(wrapper, tree)


# ---------------------------------------------------------------------------
# Deterministic hashing / RNG
# ---------------------------------------------------------------------------


def stable_hash(*parts: Any) -> int:
    """Deterministic 63-bit hash of a sequence of printable parts."""
    h = hashlib.blake2b("\x1f".join(str(p) for p in parts).encode(), digest_size=8)
    return int.from_bytes(h.digest(), "little") & 0x7FFFFFFFFFFFFFFF


def fold_key(key: jax.Array, *parts: Any) -> jax.Array:
    """Fold arbitrary identifiers into a PRNG key deterministically."""
    return jax.random.fold_in(key, stable_hash(*parts) % (2**31 - 1))


def content_digest(tree: PyTree) -> str:
    """Hex digest of the concrete values of a pytree (host-side)."""
    h = hashlib.blake2b(digest_size=16)
    for path, leaf in zip(tree_paths(tree), jax.tree_util.tree_leaves(tree)):
        h.update(path.encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# Math helpers
# ---------------------------------------------------------------------------


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(x: int, m: int) -> int:
    return cdiv(x, m) * m


def cosine_similarity(a: jax.Array, b: jax.Array, eps: float = 1e-8) -> jax.Array:
    """Cosine similarity of two flattened tensors (validator agreement metric,

    paper §2.3: 'Forward and backwards passes are checked against the
    submitted miner activations using a cosine similarity')."""
    a = a.reshape(-1).astype(jnp.float32)
    b = b.reshape(-1).astype(jnp.float32)
    na = jnp.linalg.norm(a)
    nb = jnp.linalg.norm(b)
    cos = jnp.vdot(a, b) / jnp.maximum(na * nb, eps)
    # two (near-)zero tensors agree by convention (an honest miner fed a
    # zeroed activation by an upstream free-rider reproduces zeros exactly)
    return jnp.where((na < 1e-6) & (nb < 1e-6), 1.0, cos)


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f} {unit}"
        n /= 1024.0
    return f"{n:.2f} PiB"


@dataclasses.dataclass(frozen=True)
class DTypePolicy:
    """Mixed-precision policy: params/compute/wire dtypes."""
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    wire_dtype: Any = jnp.bfloat16   # activations on the wire (paper: bf16 = 2x)
    logits_dtype: Any = jnp.float32  # losses always reduced in fp32
