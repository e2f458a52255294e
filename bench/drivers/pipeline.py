"""Driver of the on-mesh pipeline: the training step of ``repro.core.pipeline``
as ``launch/train.py --strategy pipeline`` builds it.

Parameters are stage-stacked over a ("data", "model") mesh of the cell's
chips.  The benchmark makes them itself, from the seed, in one jitted call
placed in the program's own layout (``reference.weight_maker`` over
``init_pipeline_params``' shapes and ``pipeline_param_shardings``).  One
jitted step runs the schedule's loss and gradients and the SGD update of
``launch/train.py``, with the old parameters donated to the new.

Set-up runs the first ``FOLLOWED`` steps, which compile, and reads, as they
run:
  losses    the loss of each step
  grad1     per-leaf norms of the change after step 1, over the learning
            rate: under SGD, the first gradient
  change2   per-leaf norms of the change after step 2
Norms are per stage, and per layer under the blocks, for the stacked
leaves (key ``<leaf>/<stage>[/<layer>]``) and whole for the shared ones
(embedding, head, final norm).  The window then runs steps until its time
is up and waits for the last.

The check follows the same steps with the plain reference, stage by stage
since the whole model does not fit one chip: stage s on the chip that holds
stage s, one microbatch row at a time, in pieces (IOTA's entry, each of the
family's layers, given to its ``blocks`` with its model-wide index, the exit
or the head with the loss).  Every row's forward
through stages 0 .. P-2 comes first, so the chips work on different rows at
once; then each row's backward, last stage first, each piece re-running its
forward under ``jax.vjp``.  The first stage's decode and the last stage's
encode, which the program leaves unused, are not in the reference.
"""
from __future__ import annotations

import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import models
from bench.lib import compare, flops
from bench.lib import reference as ref
from bench.lib.spans import Spans
from bench.lib.tokens import TokenBatches

FOLLOWED = 2        # steps the losses, gradients and changes are read over
STAGED = "stages/"  # leaves stacked on a leading stage axis
BLOCKS = STAGED + "blocks/"     # ... and on a layer axis after it
ENTER = ("embeds/embed", "stages/w_up_prev", "stages/alpha_dec")
# the reference's name of a leaf of the program's layout, where they differ
REF_NAME = {"stages/w_up_prev": "w_up", "embeds/unembed": "unembed"}


def sgd(p, g, lr: float):
    """``launch/train.py``'s update."""
    return (p - lr * g.astype(jnp.float32)).astype(p.dtype)


def build_step(family, config_name: str, m: dict, t: dict, mesh,
               z_loss: float):
    """(shapes, shardings, step) of the cell: ``shapes`` are the parameters'
    as ``init_pipeline_params`` makes them, ``shardings`` their layout on
    ``mesh``, and ``step(params, batch) -> (params, loss)`` is jitted."""
    from repro.core.pipeline import (PipelineSpec, init_pipeline_params,
                                     pipeline_loss_and_grads,
                                     pipeline_param_shardings)
    cfg = family.program_config(config_name, m)
    spec = PipelineSpec(n_stages=t["n_stages"],
                        n_microbatches=t["microbatches"], compress=True,
                        bottleneck_dim=t["bottleneck_dim"],
                        schedule=t["schedule"], wire_codec=t["wire_codec"])
    shapes = jax.eval_shape(
        functools.partial(init_pipeline_params, cfg=cfg, spec=spec),
        jax.random.key(0))
    shardings = pipeline_param_shardings(shapes, mesh)
    lr = t["lr"]

    def step(params, batch):
        loss, grads = pipeline_loss_and_grads(params, batch, cfg, spec, mesh,
                                              z_loss=z_loss)
        return jax.tree.map(lambda p, g: sgd(p, g, lr), params, grads), loss

    return shapes, shardings, jax.jit(step, donate_argnums=0)


def make_mesh(devices, t: dict):
    from jax.sharding import Mesh
    n = t["n_stages"]
    return Mesh(np.asarray(devices).reshape(len(devices) // n, n),
                ("data", "model"))


def flat(tree) -> dict:
    return {ref.leaf_name(p): a
            for p, a in jax.tree_util.tree_flatten_with_path(tree)[0]}


def leading(name: str) -> int:
    """How many leading axes of the leaf ``name`` the program's layout
    stacks: the stage's, and the layer's under the blocks."""
    return 2 if name.startswith(BLOCKS) else 1 if name.startswith(STAGED) \
        else 0


@jax.jit
def change_norms(a: dict, b: dict) -> dict:
    """Per-leaf norms of a - b, one per stage and layer along the axes the
    layout stacks."""
    def norm(name, x, y):
        d = jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32))
        return jnp.sqrt(jnp.sum(d, axis=tuple(range(leading(name), d.ndim))))
    return {k: norm(k, a[k], b[k]) for k in a}


@functools.partial(jax.jit, static_argnums=0)
def moved_from(trees, params, key) -> dict:
    """``change_norms`` of ``params`` against the first tree that
    ``trees(key)`` (``weight_maker``'s ``make.trees``) draws, in one jitted
    call: the compiler draws a leaf where its norm needs it, so the
    parameters are not held twice."""
    return change_norms(flat(params), flat(trees(key)[0]))


def keyed(norms: dict, at: tuple = (0, 0)) -> dict:
    """{leaf/stage/layer: norm} from ``change_norms``: the stage and layer
    are the stacked axes' indices plus ``at``, the place of a slice's first
    stage and layer."""
    out = {}
    for name, v in norms.items():
        v = np.asarray(v)
        for idx in np.ndindex(v.shape):
            place = (a + i for a, i in zip(at, idx))
            out["/".join([name, *map(str, place)])] = float(v[idx])
    return out


def stage_names(names, s: int, n_stages: int) -> list:
    """The leaves of the program's layout that the reference's stage ``s``
    uses: the first stage takes the embedding and has no decode, the last
    takes the head and has no encode."""
    unused = {0: ("stages/w_up_prev", "stages/alpha_dec"),
              n_stages - 1: ("stages/w_down", "stages/enc_norm")}
    shared = {0: ("embeds/embed",),
              n_stages - 1: ("embeds/unembed", "final_norm")}
    return [k for k in names if k.startswith(STAGED)
            and k not in unused.get(s, ())] + list(shared.get(s, ()))


def pieces(leaves: dict) -> list:
    """One stage's leaves, cut to the stage, as the reference runs them:
    [("enter", 0, ...), ("layer", i, ...) for each layer i of the stage,
    ("leave", 0, ...)].  A layer's leaves keep a leading stage axis and
    layer axis of 1."""
    n_layers = next(a.shape[1] for k, a in leaves.items()
                    if k.startswith(BLOCKS))
    out = [("enter", 0, {k: a for k, a in leaves.items() if k in ENTER})]
    out += [("layer", layer, {k: a[:, layer:layer + 1]
                              for k, a in leaves.items()
                              if k.startswith(BLOCKS)})
            for layer in range(n_layers)]
    out.append(("leave", 0, {k: a for k, a in leaves.items()
                             if k not in ENTER and not k.startswith(BLOCKS)}))
    return out


def reference_tree(p: dict) -> dict:
    """The reference's tree of one piece's leaves of the program's layout:
    the stage axis of stacked leaves dropped, the layer axis kept."""
    tree: dict = {}
    for name, a in p.items():
        if name.startswith(STAGED):
            a = a[0]
        path = REF_NAME.get(name, name.removeprefix(STAGED)).split("/")
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = a
    return tree


class PipelineCell:
    end_to_end = "pipeline_tokens_per_s"

    def __init__(self, ctx: dict):
        self.ctx = ctx
        self.seed = ctx["seed"]
        config = ctx["config"]
        self.m = config["model"]
        self.z_loss = config["objective"]["z_loss"]
        self.family_name = config["family"]
        self.family = models.get(self.family_name)
        self.t = ctx["traffic"]
        self.spans = Spans()
        t = self.t
        rows = t["batch_size"]
        # planted faults of the check, as the reference put in the
        # program's place: rows of every batch it trains on, or every
        # hand-off between chips delivering zeros
        self.faults = {
            "half_batch": {"rows": tuple(range(rows // 2))},
            "drain": {"rows": tuple(range(rows - rows // t["microbatches"]))},
            "exchange": {"exchange": False},
        }

    def batches(self) -> TokenBatches:
        t = self.t
        return TokenBatches(self.seed, self.m["vocab_size"], t["batch_size"],
                            t["seq_len"], t["zipf_exponent"])

    # ------------------------------------------------------------------

    def setup(self) -> None:
        from jax.sharding import NamedSharding, PartitionSpec
        t = self.t
        self.mesh = make_mesh(jax.devices()[: self.ctx["chips"]], t)
        self.shapes, self.shardings, self.step = build_step(
            self.family, self.ctx["config_name"], self.m, t, self.mesh,
            self.z_loss)
        self.make = ref.weight_maker([self.shapes], self.family.init_leaf,
                                     [self.shardings])
        self.params = self.make(self.seed)[0]
        self.batch_sharding = NamedSharding(self.mesh, PartitionSpec())
        self.corpus = self.batches()
        self.tick = 0
        lr, losses, self.check_s = t["lr"], [], 0.0
        self.prog = {}
        for n in range(1, FOLLOWED + 1):
            self.params, loss = self.step(self.params, self._batch())
            losses.append(float(loss))
            t0 = time.perf_counter()
            moved = keyed(moved_from(self.make.trees, self.params,
                                     ref.seed_key(self.seed)))
            self.check_s += time.perf_counter() - t0
            if n == 1:
                self.prog["grad1"] = {k: v / lr for k, v in moved.items()}
        self.prog["change2"] = moved
        self.prog["losses"] = losses

    def _batch(self) -> dict:
        b = jax.device_put(self.corpus.batch(self.tick), self.batch_sharding)
        self.tick += 1
        return b

    def window(self, seconds: float) -> dict:
        t = self.t
        losses = []
        batch = self._batch()
        start = time.perf_counter()
        with self.spans.span("window"):
            while time.perf_counter() - start < seconds:
                self.params, loss = self.step(self.params, batch)
                batch = self._batch()
                losses.append(float(loss))
            jax.block_until_ready(self.params)
        window_s = time.perf_counter() - start
        steps = len(losses)
        failed = int(np.sum(~np.isfinite(losses)))
        tokens = (steps - failed) * t["batch_size"] * t["seq_len"]
        self.readings = {"tokens": tokens, "steps": steps,
                         "trained_seconds": window_s}
        return {"attempted": steps, "failed": failed,
                "metrics": {self.end_to_end: tokens / window_s}}

    def context(self) -> dict:
        m, t = self.m, self.t
        return dict(
            self.readings, model=m,
            flops_per_token=flops.train_flops_per_token(
                m, self.family, m["num_hidden_layers"], t["seq_len"],
                t["bottleneck_dim"], t["n_stages"] - 1),
            attention=self.family.attention_shape(
                m, t["batch_size"] // t["microbatches"], t["seq_len"]))

    # ------------------------------------------------------------------

    def release(self) -> None:
        del self.params, self.step
        gc.collect()

    def check(self, limits: dict) -> list:
        want = self.reference("f32")
        return compare.rows(self.numbers(self.prog, want), limits)

    def numbers(self, prog: dict, want: dict) -> dict:
        """Every number the check can compare; the cell's limits file
        names the ones it does."""
        nought = compare.nought_leaves(want["grad1"])
        return {
            "loss1_gap": compare.loss_gap(prog["losses"][:1],
                                          want["losses"][:1]),
            "loss_gap": compare.loss_gap(prog["losses"], want["losses"]),
            "grad1_gap": compare.worst_leaf(prog["grad1"], want["grad1"],
                                            nought),
            "change2_gap": compare.worst_leaf(prog["change2"],
                                              want["change2"], nought),
        }

    def stage_pieces(self) -> list:
        """Per stage: (device, ``pieces``) of the leaves the stage uses
        (``stage_names``), made from the seed in the program's layout and
        cut where each chip holds them."""
        w = flat(self.make(self.seed)[0])
        out = []
        for s in range(self.t["n_stages"]):
            dev = next(sh.device
                       for sh in w["stages/enc_norm"].addressable_shards
                       if sh.index[0].start == s)
            out.append((dev, pieces({
                k: next(sh.data for sh in w[k].addressable_shards
                        if sh.device == dev)
                for k in stage_names(w, s, self.t["n_stages"])})))
        return out

    def reference(self, mode: str, rows=None, exchange: bool = True) -> dict:
        """Follow the first ``FOLLOWED`` steps with the plain reference in
        ``mode``, each batch's loss the mean over ``rows`` (all of them; a
        planted fault fewer), and read what set-up reads.  A planted fault
        without ``exchange`` hands each stage zeros for the code and for
        its cotangent, as the program's hand-offs left out would."""
        t = self.t
        stages = self.stage_pieces()
        devs = [d for d, _ in stages]
        # each piece's kind and the model-wide index of its layer
        per_stage = self.m["num_hidden_layers"] // t["n_stages"]
        kinds = [[(k, s * per_stage + i) for k, i, _ in ps]
                 for s, (_, ps) in enumerate(stages)]
        places = [[(s, i) for _, i, _ in ps] for s, (_, ps) in
                  enumerate(stages)]
        params = [[p for _, _, p in ps] for _, ps in stages]
        w0 = [list(ps) for ps in params]
        corpus = self.batches()
        rows = tuple(range(t["batch_size"])) if rows is None else rows
        out = {"losses": []}
        for n in range(1, FOLLOWED + 1):
            b = corpus.batch(n - 1)
            loss, grads = self._ref_grads(kinds, params, b, rows, devs,
                                          mode, exchange)
            params = [[_sgd_tree(p, g, t["lr"]) for p, g in zip(ps, gs)]
                      for ps, gs in zip(params, grads)]
            out["losses"].append(loss)
            moved = {}
            for ps, p0s, at in zip(params, w0, places):
                for p, p0, place in zip(ps, p0s, at):
                    moved.update(keyed(change_norms(p, p0), at=place))
            if n == 1:
                out["grad1"] = {k: v / t["lr"] for k, v in moved.items()}
        out["change2"] = moved
        return out

    def _ref_grads(self, kinds, params, b, rows, devs, mode, exchange):
        """(mean loss over ``rows``, per-piece gradients of it).  Every
        row's forward first, keeping each piece's input, then every row's
        backward, last stage first; a piece recomputes its forward."""
        last = len(params) - 1
        st = dict(m=tuple(sorted(self.m.items())), mode=mode,
                  family=self.family_name)

        def hand(x, s):
            x = jax.device_put(x, devs[s])
            return x if exchange else jnp.zeros_like(x)

        acc = [[_zeros(p) for p in ps] for ps in params]
        saved = {}
        for r in rows:
            x = b["tokens"][r:r + 1]
            for s, ks in enumerate(kinds):
                x = jax.device_put(x, devs[s]) if s == 0 else hand(x, s)
                for i, (kind, first) in enumerate(ks):
                    saved[r, s, i] = x
                    if not (s == last and kind == "leave"):
                        x = _run(params[s][i], x, kind=kind, first=first,
                                 **st)
        losses = []
        for r in rows:
            labels = jax.device_put(b["labels"][r:r + 1], devs[last])
            ct = None
            for s in range(last, -1, -1):
                ct = None if ct is None else hand(ct, s)
                for i in range(len(kinds[s]) - 1, -1, -1):
                    x = saved.pop((r, s, i))
                    kind, first = kinds[s][i]
                    if ct is None:
                        loss, acc[s][i], ct = _pull_loss(
                            params[s][i], x, labels, acc[s][i],
                            1.0 / len(rows), z_loss=self.z_loss,
                            first=first, **st)
                        losses.append(loss)
                    else:
                        acc[s][i], ct = _pull(params[s][i], x, ct, acc[s][i],
                                              kind=kind, first=first, **st)
        return float(np.mean([float(x) for x in losses])), acc


# the reference's programs, one piece of one stage on one chip; ``m`` is
# the model's configuration as a hashable tuple of items


@jax.jit
def _zeros(p):
    return jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), p)


@functools.partial(jax.jit, static_argnames=("lr",))
def _sgd_tree(p, g, lr):
    return jax.tree.map(lambda a, b: sgd(a, b, lr), p, g)


def _piece(p, x, kind, first, m, mode, family):
    """One piece; a layer is the model's layer ``first``."""
    tree, m = reference_tree(p), dict(m)
    if kind == "enter":
        return ref.stage_in(tree, x, m, mode)
    if kind == "layer":
        return models.get(family).blocks(tree["blocks"], x, m, mode, first)
    return ref.stage_out(tree, x, m, mode)


PIECE_ARGS = ("kind", "first", "m", "mode", "family")


@functools.partial(jax.jit, static_argnames=PIECE_ARGS)
def _run(p, x, kind, first, m, mode, family):
    return _piece(p, x, kind, first, m, mode, family)


@functools.partial(jax.jit, static_argnames=PIECE_ARGS, donate_argnums=(3,))
def _pull(p, x, ct, acc, kind, first, m, mode, family):
    """(acc + the piece's gradient, the cotangent of its input; none for
    tokens)."""
    def piece(p, x):
        return _piece(p, x, kind, first, m, mode, family)

    if jnp.issubdtype(x.dtype, jnp.integer):
        _, vjp = jax.vjp(lambda p: piece(p, x), p)
        (g,), g_x = vjp(ct), None
    else:
        _, vjp = jax.vjp(piece, p, x)
        g, g_x = vjp(ct)
    return jax.tree.map(jnp.add, acc, g), g_x


@functools.partial(jax.jit,
                   static_argnames=("first", "m", "mode", "family", "z_loss"),
                   donate_argnums=(3,))
def _pull_loss(p, x, labels, acc, scale, first, m, mode, family, z_loss):
    """The last stage's exit with the loss: (the row's loss, acc + scale x
    its gradient, the cotangent of its input)."""
    def loss(p, x):
        return ref.token_loss(
            _piece(p, x, "leave", first, m, mode, family), labels, z_loss)
    value, vjp = jax.vjp(loss, p, x)
    g, g_x = vjp(jnp.asarray(scale, jnp.float32))
    return value, jax.tree.map(jnp.add, acc, g), g_x


def build(ctx: dict) -> PipelineCell:
    return PipelineCell(ctx)
