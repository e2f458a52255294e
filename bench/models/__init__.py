"""Model families: what the benchmark knows of one kind of model.

A configuration file names its family (``"family": "dense_decoder"``), and
``get`` resolves the name to a module: a bare name to
``bench/models/<name>.py``, a dotted name to that module (a family kept
beside a test, say).  The drivers, the reference's shared parts
(``bench/lib/reference.py``) and the FLOP count's shared terms
(``bench/lib/flops.py``) know nothing of any one family; everything that
does lives in its module, which exposes:

  program_config(name, m) -> repro.configs.base.ModelConfig
        how the program under test is configured for the ``model`` section
        ``m`` of the configuration file; the only place a family touches
        ``src/``
  init_leaf(key, name, shape, dtype) -> array
        how ``reference.make_weights`` draws the leaf ``name`` (its path in
        the parameter tree, "/"-joined) from ``key``
  blocks(pb, x, m, mode, first) -> x
        the plain float32 reference of consecutive layers of one stage:
        ``pb`` holds them stacked on a leading layer axis, ``first`` is
        the model-wide index of the first of them (a Python int: stage
        s of n-layer stages starts at s * n), so that a family whose
        layer kind depends on the layer's place (a hybrid period, leading
        dense layers) picks it; ``x`` is (batch, seq, hidden) float32,
        and every matrix product goes through
        ``reference.einsum(spec, a, b, mode)``.  The swarm's reference
        hands it a stage's whole stack, the pipeline's one layer at a time
  block_flops_per_token(m, n_layers, seq_len) -> int
        model FLOPs per trained token of ``n_layers`` such layers, under
        ``bench/lib/flops.py``'s convention
  attention_shape(m, batch, seq) -> dict | None
        the ``attention`` entry of a driver's context, which the flash
        roofline readers read (batch, seq, heads, kv_heads, head_dim), or
        None where the family runs no such kernel

A new family is new files only: its module here, its configuration in
``bench/configs/``, its traffic, cells and readers.
"""
from __future__ import annotations

import importlib


def get(name: str):
    """The family module ``name`` names."""
    return importlib.import_module(name if "." in name
                                   else f"bench.models.{name}")
