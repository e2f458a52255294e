"""The globally accessible database of paper §2 ('S3 bucket', Fig 6).

All miner/validator/orchestrator traffic flows through here, which is what
makes interactions auditable ('making it easy to trace the movement of
information').  In-process dict with:
  * content digests (tamper evidence for validators): SHA-256 over each
    leaf's raw bytes, leaves in ``tree_leaves`` order, truncated to its
    first 96 bits (24 hex characters).  Every transport and the socket
    server call the one ``_digest``, so both ends of a wire agree;
  * byte accounting per (namespace, direction) — the §5.3 transfer-analysis
    benchmark reads these counters,
  * optional wire codec applied on put (compressed sharing stage).
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections import defaultdict
from typing import Any, Optional

import jax
from jax.flatten_util import ravel_pytree
import numpy as np

from repro.common import span
from repro.core import compression


class StoreKeyError(KeyError):
    """Missing store key, with enough context to debug a routing bug:
    the key, who asked, and the nearest prefix that *does* exist (so an
    off-by-one epoch/tick/uid is visible at a glance)."""

    def __init__(self, key: str, actor: str = "?",
                 nearest_prefix: str = "", nearest_count: int = 0):
        self.key = key
        self.actor = actor
        self.nearest_prefix = nearest_prefix
        self.nearest_count = nearest_count
        if nearest_prefix:
            hint = (f"nearest existing prefix {nearest_prefix!r} "
                    f"({nearest_count} keys)")
        else:
            hint = "store is empty" if nearest_count == 0 else \
                f"no shared prefix ({nearest_count} keys in store)"
        super().__init__(
            f"store key not found: {key!r} (requested by {actor!r}; {hint})")

    def __str__(self) -> str:  # KeyError.__str__ repr()s the arg; undo that
        return self.args[0]


@dataclasses.dataclass
class StoreEntry:
    payload: Any
    nbytes: int
    digest: str
    meta: dict


def _nbytes(value: Any) -> int:
    total = 0
    for leaf in jax.tree_util.tree_leaves(value):
        arr = np.asarray(leaf)
        total += arr.nbytes
    return total


def _digest(value: Any) -> str:
    """Hash each leaf's host buffer in place: no ``tobytes`` copy, and
    SHA-256 runs on the CPU's hash unit where it has one.  The uint8 view
    is what lets ``hashlib`` read dtypes the buffer protocol refuses
    (bfloat16); ``reshape(-1)`` covers 0-d leaves.  An object leaf (the
    in-process store takes payloads serde cannot encode), which no view
    can read, is hashed by its ``tobytes``."""
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(value):
        arr = np.ascontiguousarray(np.asarray(leaf))
        h.update(arr.tobytes() if arr.dtype.hasobject
                 else arr.reshape(-1).view(np.uint8))
    return h.hexdigest()[:24]


class StateStore:
    def __init__(self):
        self._data: dict[str, StoreEntry] = {}
        self.uploaded = defaultdict(int)      # namespace -> bytes
        self.downloaded = defaultdict(int)
        self.uploads_by_actor = defaultdict(int)
        self.downloads_by_actor = defaultdict(int)

    @staticmethod
    def _ns(key: str) -> str:
        return key.split("/", 1)[0]

    @staticmethod
    def _under(key: str, prefix: str) -> bool:
        """Segment-boundary prefix match: ``weights/ep1`` covers
        ``weights/ep1/...`` and the exact key, but *not* ``weights/ep10/...``
        (a raw ``startswith`` collided ep1 with ep10+ and s1 with s10+,
        so epoch GC and stage-scoped audit walks leaked across segments).
        A trailing-``/`` prefix keeps its literal meaning; the empty prefix
        covers everything."""
        if not prefix:
            return True
        if prefix.endswith("/"):
            return key.startswith(prefix)
        return key == prefix or key.startswith(prefix + "/")

    def put(self, key: str, value: Any, actor: str = "?",
            codec: Optional[str] = None,
            meta: Optional[dict] = None) -> StoreEntry:
        """Store ``value``; returns the full ``StoreEntry`` so callers that
        need the byte count (the simulated-network hot loop) don't pay a
        second lookup.  The entry carries the digest for tamper evidence."""
        with span("store.put"):
            if codec and codec != "none":
                with span("store.encode"):
                    flat, _ = ravel_pytree(value)
                    value = compression.encode(flat, codec)
            # the first np.asarray of a device value is its copy to the
            # host, and waits for the program that produces it
            with span("store.copy"):
                nbytes = _nbytes(value)
            with span("store.hash", bytes=nbytes):
                digest = _digest(value)
            entry = StoreEntry(value, nbytes, digest,
                               dict(meta or {}, codec=codec or "none"))
            self._data[key] = entry
            self.uploaded[self._ns(key)] += nbytes
            self.uploads_by_actor[actor] += nbytes
            return entry

    def _nearest_prefix(self, key: str) -> tuple[str, int]:
        """Longest '/'-segment prefix of ``key`` under which keys exist."""
        parts = key.split("/")
        for i in range(len(parts), 0, -1):
            p = "/".join(parts[:i])
            n = sum(1 for k in self._data if self._under(k, p))
            if n:
                return p, n
        return "", len(self._data)

    def _missing(self, key: str, actor: str) -> StoreKeyError:
        prefix, count = self._nearest_prefix(key)
        return StoreKeyError(key, actor, prefix, count)

    def get(self, key: str, actor: str = "?") -> Any:
        return self.fetch_entry(key, actor).payload

    def fetch_entry(self, key: str, actor: str = "?") -> StoreEntry:
        """Accounted read returning the full entry (payload + nbytes +
        digest) — one dict lookup for callers that also need the size."""
        entry = self._data.get(key)
        if entry is None:
            raise self._missing(key, actor)
        with span("store.get", bytes=entry.nbytes):
            self.downloaded[self._ns(key)] += entry.nbytes
            self.downloads_by_actor[actor] += entry.nbytes
            return entry

    def get_entry(self, key: str) -> StoreEntry:
        entry = self._data.get(key)
        if entry is None:
            raise self._missing(key, "?")
        return entry

    def exists(self, key: str) -> bool:
        return key in self._data

    def delete_prefix(self, prefix: str) -> int:
        doomed = [k for k in self._data if self._under(k, prefix)]
        for k in doomed:
            del self._data[k]
        return len(doomed)

    def keys(self, prefix: str = "") -> list[str]:
        return sorted(k for k in self._data if self._under(k, prefix))

    def traffic_report(self) -> dict:
        return {
            "uploaded": dict(self.uploaded),
            "downloaded": dict(self.downloaded),
            "by_actor_up": dict(self.uploads_by_actor),
            "by_actor_down": dict(self.downloads_by_actor),
            "total_bytes": (sum(self.uploaded.values())
                            + sum(self.downloaded.values())),
        }
