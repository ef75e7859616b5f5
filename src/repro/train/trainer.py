"""Trainer: wires model + data + optimizer + sync mode + checkpointing."""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig, RunConfig
from ..data.pipeline import batches, make_source
from ..dist.sharding import batch_specs, on_mesh, param_specs
from ..launch.mesh import dp_axes, make_local_mesh
from ..models import Model
from ..optim.optimizers import get_optimizer
from ..optim.schedules import warmup_cosine
from . import checkpoint as ckpt_lib
from .train_step import (
    make_bcast_train_step,
    make_compressed_allreduce_train_step,
    make_degraded_psum_train_step,
    make_overlap_allreduce_train_step,
    make_train_step,
    make_tuned_allreduce_train_step,
    with_error_feedback,
)

__all__ = ["Trainer"]


class Trainer:
    def __init__(
        self,
        cfg: ModelConfig,
        run: RunConfig,
        *,
        mesh=None,
        data_path: Optional[str] = None,
        ckpt_dir: Optional[str] = None,
        health=None,
    ):
        self.cfg = cfg
        self.run = run
        self.model = Model(cfg)
        self.mesh = mesh if mesh is not None else make_local_mesh(1)
        self.optimizer = get_optimizer(run.optimizer, run.weight_decay)
        if run.sync_mode == "compressed_allreduce":
            # the EF residual rides in opt_state['ef'] so it checkpoints,
            # restores, and donates with the rest of the optimizer state
            self.optimizer = with_error_feedback(self.optimizer)
        self.lr_fn = warmup_cosine(run.learning_rate, run.warmup_steps, run.total_steps)
        self.source = make_source(cfg, path=data_path, seed=run.seed)
        self.ckpt_dir = ckpt_dir
        # comm.faults.MeshHealth for the data-parallel world; a degraded
        # report overrides sync_mode with the psum-over-survivors fallback
        self.health = health
        self._build()

    def _build(self):
        mesh = self.mesh
        explicit_sync = {
            "param_bcast": make_bcast_train_step,
            "tuned_allreduce": make_tuned_allreduce_train_step,
            "overlap_allreduce": make_overlap_allreduce_train_step,
            "compressed_allreduce": make_compressed_allreduce_train_step,
        }
        if self.health is not None and not self.health.healthy and self.health.dead_ranks:
            # graceful degradation: the tuned schedules assume every rank is
            # reachable, so a dead-rank report routes gradient sync to the
            # masked psum with survivor-count normalization until a replan
            print(
                f"trainer: mesh degraded (dead ranks {self.health.dead_ranks}); "
                f"sync_mode {self.run.sync_mode!r} falls back to psum-over-survivors",
                flush=True,
            )
            step_fn = make_degraded_psum_train_step(
                self.model, self.run, self.optimizer, self.lr_fn, mesh,
                health=self.health,
            )
            self._pspecs = jax.tree.map(lambda _: P(), self.model.param_shapes())
        elif self.run.sync_mode in explicit_sync:
            # calibrated empirical decisions (Tuner.save format) when the
            # run points at a table; analytic otherwise
            from ..core.tuner import Tuner

            tuner = Tuner.load(self.run.tuner_table) if self.run.tuner_table else None
            step_fn = explicit_sync[self.run.sync_mode](
                self.model, self.run, self.optimizer, self.lr_fn, mesh, tuner=tuner
            )
            self._pspecs = jax.tree.map(
                lambda _: P(), self.model.param_shapes()
            )
        else:
            step_fn = make_train_step(self.model, self.run, self.optimizer, self.lr_fn)
            self._pspecs = param_specs(self.model.param_shapes(), mesh)
        self._step_fn = jax.jit(on_mesh(step_fn, mesh), donate_argnums=(0, 1))

    def init_state(self, seed: Optional[int] = None):
        seed = self.run.seed if seed is None else seed
        with jax.set_mesh(self.mesh):
            params = jax.jit(
                self.model.init,
                out_shardings=jax.tree.map(lambda s: NamedSharding(self.mesh, s), self._pspecs),
            )(jax.random.PRNGKey(seed))
            opt_state = jax.jit(
                self.optimizer.init,
            )(params)
        return params, opt_state

    def restore_or_init(self):
        if self.ckpt_dir:
            step = ckpt_lib.latest_step(self.ckpt_dir)
            if step is not None:
                params_like = self.model.param_shapes()
                params = ckpt_lib.restore_checkpoint(self.ckpt_dir, step, params_like)
                opt_like = jax.eval_shape(self.optimizer.init, params_like)
                opt = ckpt_lib.restore_checkpoint(
                    self.ckpt_dir + "/opt", step, opt_like
                )
                return params, opt, step
        params, opt = self.init_state()
        return params, opt, 0

    def train(self, *, batch: int, seq: int, steps: int, log_every: int = 10, ckpt_every: int = 0):
        params, opt_state, start = self.restore_or_init()
        it = batches(self.source, self.cfg, batch=batch, seq=seq, start_step=start)
        bspecs = None
        history = []
        t0 = time.time()
        with self.mesh:
            for step in range(start, start + steps):
                b = next(it)
                if bspecs is None:
                    bspecs = batch_specs(b, self.mesh)
                b = jax.tree.map(
                    lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)), b, bspecs
                )
                params, opt_state, metrics = self._step_fn(params, opt_state, b)
                if log_every and (step % log_every == 0 or step == start + steps - 1):
                    m = {k: float(v) for k, v in metrics.items()}
                    dt = time.time() - t0
                    history.append({"step": step, "time_s": dt, **m})
                    print(
                        f"step {step:6d} loss {m['loss']:.4f} nll {m.get('nll', 0.0):.4f} "
                        f"gnorm {m['grad_norm']:.2f} lr {m['lr']:.2e} ({dt:.1f}s)",
                        flush=True,
                    )
                if ckpt_every and self.ckpt_dir and (step + 1) % ckpt_every == 0:
                    ckpt_lib.save_checkpoint(self.ckpt_dir, step + 1, params)
                    ckpt_lib.save_checkpoint(self.ckpt_dir + "/opt", step + 1, opt_state)
        return params, opt_state, history
