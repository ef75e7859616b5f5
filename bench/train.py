"""Training cells: the program's own jitted step, driven from set-up through
the measured window, then held to the plain reference.

Set-up builds one ``Trainer`` and gives it weights made here from the seed.
Its first ``checked_steps`` steps go through the same call and feed as the
window, synchronously, and leave behind what the reference is compared
with: each step's loss, the first gradient as AdamW's first moment holds it
after step one, and each parameter's change after the checked steps. More
steps warm the pipelined loop, then the window runs with one step in
flight: step k+1 is dispatched before step k's loss is read.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .generator import TrainFeed
from .reference.common import F32, leaf_norms, n_params

ANNOTATE = jax.profiler.TraceAnnotation


def model_config(config: dict):
    """The program's ModelConfig from a configuration file; a key that is
    neither a field nor one of the file's notes is an error."""
    from repro.configs.base import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    notes = {"paper", "reference", "reduced", "departures", "rehearsal"}
    unknown = set(config) - fields - notes
    if unknown:
        raise KeyError(f"configuration keys the program does not have: {sorted(unknown)}")
    kw = {k: (tuple(v) if isinstance(v, list) else v) for k, v in config.items() if k in fields}
    return ModelConfig(**kw)


def run_config(traffic: dict, seed: int):
    from repro.configs.base import RunConfig

    opt = traffic["optimizer"]
    return RunConfig(learning_rate=opt["learning_rate"], weight_decay=opt["weight_decay"],
                     warmup_steps=opt["warmup_steps"], total_steps=opt["total_steps"],
                     optimizer=opt["name"], sync_mode=traffic["sync_mode"],
                     remat=traffic["remat"], seed=seed % (2**31))


def data_mesh(devs) -> Mesh:
    from repro.dist.topology import DP_AXES, TP_AXIS

    auto = jax.sharding.AxisType.Auto
    return Mesh(np.array(devs).reshape(len(devs), 1), (DP_AXES[-1], TP_AXIS), axis_types=(auto, auto))


@dataclasses.dataclass
class TraceContext:
    """What a per-layer metric's reader is given: the reduced trace, the
    traced window [lo, hi] on its clock, the steps in it, and the cell's
    chips, model FLOPs per step and chip peaks. ``log`` prints a line
    before the result."""
    trace: dict
    lo: float
    hi: float
    steps: int
    chips: int
    flops_per_step: float
    peaks: dict
    log: object


@dataclasses.dataclass
class Observed:
    """What a run leaves to compare: per-step losses, per-leaf norms of the
    first gradient and of the change over the checked steps."""
    losses: list
    grad_norms: np.ndarray
    change_norms: np.ndarray


class Program:
    """The system under test for one cell: the Trainer's compiled step and
    its state, fed from the traffic file."""

    def __init__(self, cell, devs, seed: int):
        from repro.dist.sharding import batch_specs
        from repro.train.trainer import Trainer

        t = cell.traffic
        if t["data_parallel"] != len(devs):
            raise ValueError(f"traffic wants {t['data_parallel']} data-parallel chips, the cell has {len(devs)}")
        self.cell, self.seed = cell, seed
        self.ref = cell.reference()
        self.mesh = data_mesh(devs)
        self.trainer = Trainer(model_config(cell.config), run_config(t, seed), mesh=self.mesh)
        self.step_fn = self.trainer._step_fn
        self.feed = TrainFeed(t, cell.config["vocab_size"], seed)
        self.batch_shardings = jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                                            batch_specs(self.feed(0), self.mesh))
        self.tokens_per_step = t["global_batch"] * t["seq"]
        self.k = 0

    def init_state(self, seed: int):
        """Weights made on the device in one jitted call from the seed, in
        the program's dtypes and placement; AdamW state from the program's
        own optimizer, as ``Trainer.init_state`` makes it."""
        cfg, tr = self.cell.config, self.trainer
        want = tr.model.param_shapes()
        shardings = jax.tree.map(lambda s: NamedSharding(self.mesh, s), tr._pspecs)
        with jax.set_mesh(self.mesh):
            params = jax.jit(lambda key: self.ref.init(cfg, key),
                             out_shardings=shardings)(jax.random.PRNGKey(seed))
            got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
            if jax.tree.structure(params) != jax.tree.structure(want) or got != jax.tree.map(
                    lambda a: (a.shape, a.dtype), want):
                raise ValueError("the reference's weights do not match the program's layout")
            opt = jax.jit(tr.optimizer.init)(params)
        self.params, self.opt, self.k, self.seed = params, opt, 0, seed
        self.feed.seed = seed
        self.n_params = n_params(want)

    def batch(self, k: int):
        with ANNOTATE("bench.feed"):
            rows = self.feed(k)
            return jax.tree.map(jax.device_put, rows, self.batch_shardings)

    def dispatch(self):
        b = self.batch(self.k)
        with ANNOTATE("bench.dispatch"):
            self.params, self.opt, m = self.step_fn(self.params, self.opt, b)
        self.k += 1
        return m["loss"]

    def checked_steps(self, n: int, b1: float) -> Observed:
        """The first ``n`` steps, one at a time, through the window's call."""
        norms = jax.jit(leaf_norms)
        cfg = self.cell.config
        losses, grad = [], None
        for _ in range(n):
            losses.append(float(self.dispatch()))
            if grad is None:
                grad = np.asarray(norms(self.opt["m"])) / (1.0 - b1)
        change = jax.jit(lambda p, key: leaf_norms(jax.tree.map(
            lambda a, b: a.astype(F32) - b.astype(F32), p, self.ref.init(cfg, key))))
        return Observed(losses, grad, np.asarray(change(self.params, jax.random.PRNGKey(self.seed))))

    def pipelined(self, steps: int | None = None, seconds: float | None = None):
        """Run with one step in flight until ``steps`` more have completed,
        or until a completion at least ``seconds`` after the start. Returns
        (start, completion times, losses); the start is the completion of
        the step before, with the next one already in flight, so every
        step has a gap and the first has no bubble."""
        pending, nxt = self.dispatch(), self.dispatch()
        with ANNOTATE("bench.wait"):
            float(pending)
        t0 = time.perf_counter()
        window = ANNOTATE("bench.window")   # exactly the counted steps, for a trace
        window.__enter__()
        pending = nxt
        times, losses = [], []
        while True:
            nxt = self.dispatch()
            with ANNOTATE("bench.wait"):
                losses.append(float(pending))
            times.append(time.perf_counter())
            pending = nxt
            done = (steps is not None and len(times) + 1 >= steps) or (
                seconds is not None and times[-1] - t0 >= seconds)
            if done:
                with ANNOTATE("bench.wait"):
                    losses.append(float(pending))
                jax.block_until_ready(self.params)
                times.append(time.perf_counter())
                window.__exit__(None, None, None)
                return t0, times, losses

    def free(self):
        for name in ("params", "opt", "trainer", "step_fn"):
            setattr(self, name, None)
        gc.collect()


def reference_observed(cell, devs, seed: int, fp8: bool = False, rows=None) -> Observed:
    """The plain reference's losses, first clipped gradient and change over
    the checked steps, from the same seed and feed. ``rows`` keeps only the
    first rows of every batch (a planted fault)."""
    from .reference import common

    ref, cfg, t = cell.reference(), cell.config, cell.traffic
    opt = t["optimizer"]
    mesh = Mesh(np.array(devs), ("d",))
    rep = NamedSharding(mesh, P())
    by_rows = NamedSharding(mesh, P("d", None))
    mm = common.Math(fp8=fp8)
    rows_per_block = t["reference_rows_per_device"] * len(devs)
    batch = t["global_batch"] if rows is None else rows
    rows_per_block = min(rows_per_block, batch)

    def loss(p, tokens, labels):
        return ref.loss(p, tokens, labels, cfg, mm)

    step = common.make_train_step(loss, opt, rows_per_block, row_sharding=P(None, "d", None))
    step = jax.jit(step, donate_argnums=(0, 1))
    feed = TrainFeed(t, cfg["vocab_size"], seed)
    key = jax.random.PRNGKey(seed)
    with jax.set_mesh(mesh):
        params = jax.jit(lambda k: ref.init(cfg, k), out_shardings=rep)(key)
        state = jax.jit(common.adamw_init, out_shardings=rep)(params)
        losses, grad = [], None
        for k in range(t["checked_steps"]):
            b = {n: jax.device_put(v[:batch], by_rows) for n, v in feed(k).items()}
            params, state, l, g = step(params, state, b["tokens"], b["labels"])
            losses.append(float(l))
            if grad is None:
                grad = np.asarray(g)
        change = jax.jit(lambda p, k: leaf_norms(jax.tree.map(
            lambda a, b: a.astype(F32) - b.astype(F32), p, ref.init(cfg, k))))
        out = Observed(losses, grad, np.asarray(change(params, key)))
    del params, state
    gc.collect()
    return out


def leaf_names(cell) -> list:
    """Path of every weight leaf, in flattening order."""
    ref, cfg = cell.reference(), cell.config
    shapes = jax.eval_shape(lambda k: ref.init(cfg, k), jax.random.PRNGKey(0))
    return [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]]


def flops_per_step(cell, n: int) -> float:
    t = cell.traffic
    ref = cell.reference()
    return ref.train_flops_per_token(cell.config, t["seq"], n) * t["global_batch"] * t["seq"]
