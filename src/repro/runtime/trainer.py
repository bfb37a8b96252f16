"""TrainerRuntime: model + data + optimizer wired into the coordinator.

The end-to-end driver behind ``examples/train_lm.py``, ``examples/
serve.py`` and the runtime integration tests: trains any registry
architecture (reduced or full config) under injected host failures /
stragglers / chaos scripts, with either recovery strategy, checkpoint/
restore, and a per-step report stream.

Two rollback tiers (DESIGN.md §16.7):
- in-memory — the coordinator retries a wedged step from its pre-step
  commit point (model state only mutates on step success);
- durable  — when a step exhausts its retries (:class:`StepWedged`),
  ``run`` restores the last crash-safe checkpoint and re-runs from the
  restored step, dropping reports from rolled-back steps.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs.base import ModelConfig
from repro.data.pipeline import DataState, ShardedTokenPipeline, TokenDataset
from repro.models import model as MODEL
from repro.optim.adamw import adamw_init, adamw_update
from repro.runtime.clock import Clock
from repro.runtime.coordinator import (
    Coordinator,
    RuntimeConfig,
    StepReport,
    StepWedged,
)
from repro.train.loop import TrainConfig, cross_entropy_loss


class TrainerRuntime:
    def __init__(self, cfg: ModelConfig, tc: TrainConfig,
                 rt: RuntimeConfig, *, seq_len: int = 128,
                 per_shard_batch: int = 2, seed: int = 0,
                 clock: Optional[Clock] = None, chaos=None,
                 obs=None, metrics=None):
        self.cfg = cfg
        self.tc = tc
        self.rt = rt
        self.dataset = TokenDataset(cfg.vocab_size, seq_len, seed=seed)
        self.per_shard_batch = per_shard_batch

        def loss_fn(params, batch):
            logits, aux, _ = MODEL.forward(cfg, params, batch,
                                           impl=tc.impl, remat=tc.remat)
            loss = cross_entropy_loss(logits, batch["labels"])
            return loss + aux, {"loss": loss}

        grad_val = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))
        # The jitted microbatch step, exposed for AOT inspection
        # (``grad_step.lower(params, batch).compile().memory_analysis()``).
        self.grad_step = grad_val

        def grad_fn(params, batch):
            (_, metrics), grads = grad_val(params, batch)
            return grads, metrics

        @jax.jit
        def apply_fn(state, grads):
            new_params, new_opt, opt_metrics = adamw_update(
                grads, state["opt"], state["params"],
                lr=tc.lr(), b1=tc.b1, b2=tc.b2,
                weight_decay=tc.weight_decay,
                grad_clip_norm=tc.grad_clip_norm)
            return {"params": new_params, "opt": new_opt,
                    "step": state["step"] + 1}

        def batch_fn(state: DataState) -> Dict[str, Any]:
            toks = self.dataset.batch(state.shard_id, state.offset,
                                      per_shard_batch)
            return {"tokens": jnp.asarray(toks[:, :-1]),
                    "labels": jnp.asarray(toks[:, 1:])}

        params = MODEL.init_params(cfg, jax.random.PRNGKey(seed))
        init_state = {"params": params, "opt": adamw_init(params),
                      "step": jnp.zeros((), jnp.int32)}
        shards = [DataState(seed, s, rt.n_hosts, 0)
                  for s in range(rt.n_hosts)]
        self.coord = Coordinator(
            rt, grad_fn=grad_fn, apply_fn=apply_fn, batch_fn=batch_fn,
            init_state=init_state, datastates=shards,
            clock=clock, chaos=chaos, obs=obs, metrics=metrics)
        self.ckpt = (CheckpointManager(rt.checkpoint_dir)
                     if rt.checkpoint_dir else None)
        self._start_step = 0
        if self.ckpt is not None and self.ckpt.latest_step() is not None:
            self.restore()

    # ------------------------------------------------------------------
    @property
    def state(self):
        return self.coord.state

    def restore(self) -> int:
        tree, step, meta = self.ckpt.restore(self.coord.state)
        self.coord.state = jax.tree.map(jnp.asarray, tree)
        self.coord.datastates = [
            DataState(**d) for d in meta["datastates"]]
        self._start_step = step
        return step

    def run(self, n_steps: int,
            on_step: Optional[Callable[[int, "TrainerRuntime"], None]] = None,
            max_durable_rollbacks: int = 2) -> List[StepReport]:
        reports: List[StepReport] = []
        target = self._start_step + n_steps
        i = self._start_step
        rollbacks = 0
        while i < target:
            if on_step is not None:
                on_step(i, self)
            try:
                rep = self.coord.run_step(i)
            except StepWedged:
                # durable rollback: restore the last crash-safe checkpoint
                # and re-run from there (DESIGN.md §16.7)
                if (self.ckpt is None or self.ckpt.latest_step() is None
                        or rollbacks >= max_durable_rollbacks):
                    raise
                rollbacks += 1
                self.ckpt.wait()
                step = self.restore()
                reports = [r for r in reports if r.step < step]
                i = step
                continue
            reports.append(rep)
            if self.ckpt is not None and self.rt.checkpoint_every and \
                    (i + 1) % self.rt.checkpoint_every == 0:
                self.ckpt.save_async(
                    self.coord.state, i + 1,
                    metadata={"datastates": [
                        dataclasses.asdict(d)
                        for d in self.coord.datastates]})
            i += 1
        if self.ckpt is not None:
            self.ckpt.wait()
        return reports

    # fault-injection passthroughs ---------------------------------------
    def freeze_host(self, host_id: str) -> None:
        self.coord.hosts[host_id].freeze()

    def slow_host(self, host_id: str, factor: float) -> None:
        self.coord.hosts[host_id].slow(factor)

    def mute_host(self, host_id: str, duration: float) -> None:
        self.coord.hosts[host_id].mute(duration)

    def shutdown(self) -> None:
        self.coord.shutdown()
