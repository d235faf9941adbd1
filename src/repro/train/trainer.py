"""Trainer: the end-to-end driver tying data, strategy, sharding,
train_step, metrics and checkpointing together.

Preferred entrypoint: ``repro.api.Session.train(...)`` — the Session owns
param init / checkpoint restore and threads the same params into
``generate``/``serve``. Constructing a Trainer directly (launch/train.py
pre-redesign style) still works: with ``params=None`` it initialises its
own sharded params via ``init_sharded_params``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro.checkpoint import latest_step, load_checkpoint, save_checkpoint
from repro.configs.base import ModelConfig
from repro.core import sharding as shd
from repro.core.pspec import sharding_rules
from repro.core.strategy import Strategy
from repro.data.pipeline import DataConfig, TokenDataset
from repro.launch.specs import batch_shardings
from repro.models import get_model
from repro.train.step import init_opt_state, make_train_step


def init_sharded_params(cfg: ModelConfig, strategy: Strategy, mesh: Mesh,
                        *, seed: int = 0):
    """Initialise model params jit-sharded straight onto ``mesh`` per the
    strategy's rules (no host-side full copy). Used by Trainer and by
    repro.api.Session so every execution mode shares one init path."""
    model = get_model(cfg)
    with sharding_rules(mesh, strategy.rules(mesh)):
        params = jax.jit(
            lambda k: model.init(k, cfg),
            out_shardings=jax.tree.map(
                lambda s: NamedSharding(mesh, s),
                shd.param_pspecs(
                    jax.eval_shape(lambda k: model.init(k, cfg),
                                   jax.random.key(seed)),
                    strategy, mesh)),
        )(jax.random.key(seed))
    return _unalias(params)


def _unalias(params):
    """jit dedups identical constants (e.g. the ln1/ln2 ones-vectors) into
    ONE buffer, and donation would then see the same buffer twice. Copy
    only the leaves whose buffer an earlier leaf already holds: copying
    every leaf while the originals are alive would double the peak."""
    seen = set()

    def one(x):
        ptrs = {s.data.unsafe_buffer_pointer() for s in x.addressable_shards}
        if ptrs & seen:
            return x.copy()
        seen.update(ptrs)
        return x

    return jax.tree.map(one, params)


@dataclass
class TrainConfig:
    steps: int = 100
    lr: float = 3e-4
    log_every: int = 10
    checkpoint_every: int = 0            # 0 = disabled
    checkpoint_dir: str = "checkpoints"
    seed: int = 0


class Trainer:
    def __init__(self, cfg: ModelConfig, strategy: Strategy, mesh: Mesh,
                 train_cfg: TrainConfig, data: Optional[TokenDataset] = None,
                 global_batch: int = 8, seq_len: int = 256, params=None):
        self.cfg, self.strategy, self.mesh = cfg, strategy, mesh
        self.tc = train_cfg
        self.data = data or TokenDataset(DataConfig(
            vocab_size=cfg.vocab_size, seq_len=seq_len,
            global_batch=global_batch, seed=train_cfg.seed))
        self.global_batch, self.seq_len = global_batch, seq_len

        if params is None:
            params = init_sharded_params(cfg, strategy, mesh,
                                         seed=train_cfg.seed)
        else:
            # adamw's fp32 master is an astype no-op alias of the float32
            # tree it is built from, and the step DONATES opt_state — a
            # private copy keeps the caller's (e.g. a Session's) buffers
            # alive
            params = jax.tree.map(lambda x: x.copy(), params)
        self.opt_state = init_opt_state(params, strategy)
        # the step donates params AND opt_state: master aliases ``params``
        # above, so our param tree must be a second, distinct copy
        self.params = jax.tree.map(lambda x: x.copy(), params)
        step_fn = make_train_step(cfg, strategy, lr=train_cfg.lr)
        psh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           shd.param_pspecs(params, strategy, mesh))
        osh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           shd.opt_state_pspecs(self.opt_state, params,
                                                strategy, mesh))
        # ZeRO-1 shards optimizer states differently from the params they
        # mirror — place them explicitly before the first donated step.
        self.opt_state = jax.device_put(self.opt_state, osh)
        self._osh = osh
        self.batch_sh = batch_shardings(cfg, global_batch, mesh, strategy)
        self._jit_step = jax.jit(step_fn, in_shardings=(psh, osh, None),
                                 out_shardings=(psh, osh, None),
                                 donate_argnums=(0, 1))
        self.step = 0
        self.history: list = []

    def maybe_restore(self):
        last = latest_step(self.tc.checkpoint_dir)
        if last is not None:
            self.params = load_checkpoint(self.tc.checkpoint_dir, last,
                                          self.params)
            # rebuild optimizer state: adamw derives the next params from
            # its fp32 master, so a master still holding the random init
            # would silently revert the restore on the first step. Init
            # from a copy — master must not alias the donated param tree.
            self.opt_state = jax.device_put(
                init_opt_state(jax.tree.map(lambda x: x.copy(), self.params),
                               self.strategy),
                self._osh)
            self.step = last
        return self.step

    def run(self, steps: Optional[int] = None) -> Dict[str, list]:
        steps = steps or self.tc.steps
        t0 = time.time()
        with sharding_rules(self.mesh, self.strategy.rules(self.mesh)):
            for i in range(steps):
                batch = self.data.batch(self.step)
                batch = {k: jax.device_put(v, self.batch_sh.get(k))
                         if k in self.batch_sh else v
                         for k, v in batch.items()}
                self.params, self.opt_state, metrics = self._jit_step(
                    self.params, self.opt_state, batch)
                self.step += 1
                if self.step % self.tc.log_every == 0 or i == steps - 1:
                    m = {k: float(v) for k, v in metrics.items()}
                    m.update(step=self.step,
                             wall=round(time.time() - t0, 2))
                    self.history.append(m)
                    print(f"step {self.step:5d}  loss {m['loss']:.4f}  "
                          f"grad_norm {m['grad_norm']:.3f}  "
                          f"wall {m['wall']}s", flush=True)
                if (self.tc.checkpoint_every and
                        self.step % self.tc.checkpoint_every == 0):
                    save_checkpoint(self.tc.checkpoint_dir, self.step,
                                    self.params)
        return {"history": self.history}
