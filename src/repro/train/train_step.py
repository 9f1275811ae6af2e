"""The pjit train step: microbatching, remat, clipping, optimizer update.

This is what the dry-run lowers against the production mesh and what the
Trainer drives. Gradient accumulation scans over microbatches with an
fp32 accumulator; gradient clipping is global-norm in fp32; the optional
pod-axis gradient compression (int8 + error feedback) is applied by the
launcher between grad computation and optimizer update (see
parallel/collectives.py).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..models import lm
from ..models.config import ModelConfig
from ..parallel.sharding import ShardingRules, param_shardings
from .optimizer import Optimizer, clip_by_global_norm

__all__ = ["TrainState", "make_train_step", "init_train_state",
           "train_state_shardings"]


@dataclass
class StepConfig:
    microbatches: int = 1
    remat: str = "full"            # none | dots | full
    attention_impl: str = "auto"
    clip_norm: float = 1.0
    accum_dtype: Any = jnp.float32
    unroll: int = 1                # layer-scan unroll (dry-run cost fidelity)
    micro_unroll: bool = False     # unroll the microbatch scan too (ditto)


TrainState = Dict[str, Any]  # {"params", "opt_state", "step"}


def init_train_state(cfg: ModelConfig, optimizer: Optimizer,
                     key: jax.Array) -> TrainState:
    params = lm.init_params(cfg, key)
    return {"params": params,
            "opt_state": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32)}


def abstract_train_state(cfg: ModelConfig, optimizer: Optimizer) -> TrainState:
    params = lm.abstract_params(cfg)
    opt_state = jax.eval_shape(optimizer.init, params)
    return {"params": params, "opt_state": opt_state,
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


def train_state_specs(cfg: ModelConfig, optimizer: Optimizer) -> TrainState:
    """Logical-axis tree for the whole train state."""
    pspecs = lm.param_specs(cfg)
    return {"params": pspecs,
            "opt_state": optimizer.state_specs(pspecs, lm.abstract_params(cfg)),
            "step": ()}


def train_state_shardings(cfg: ModelConfig, optimizer: Optimizer,
                          rules: ShardingRules) -> TrainState:
    """NamedShardings for the whole train state on ``rules.mesh``."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    state_abs = abstract_train_state(cfg, optimizer)
    specs = train_state_specs(cfg, optimizer)
    return {"params": param_shardings(specs["params"], rules,
                                      state_abs["params"]),
            "opt_state": param_shardings(specs["opt_state"], rules,
                                         state_abs["opt_state"]),
            "step": NamedSharding(rules.mesh, P())}


def make_train_step(cfg: ModelConfig, optimizer: Optimizer,
                    step_cfg: Optional[StepConfig] = None,
                    grad_transform: Optional[Callable] = None):
    """Returns train_step(state, batch) -> (state, metrics).

    ``grad_transform(grads) -> grads`` hook: pod-axis compression or any
    distributed-optimization trick slots in without touching this file.
    """
    sc = step_cfg or StepConfig()

    def loss_fn(params, mb):
        return lm.train_loss(cfg, params, mb, sc.attention_impl, sc.remat,
                             sc.unroll)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def single(params, batch):
        (loss, metrics), grads = grad_fn(params, batch)
        return grads, metrics

    def accumulated(params, batch):
        mu = sc.microbatches

        def reshape(x):
            return x.reshape((mu, x.shape[0] // mu) + x.shape[1:])

        mbs = jax.tree.map(reshape, batch)
        acc0 = jax.tree.map(
            lambda p: jnp.zeros(p.shape, sc.accum_dtype), params)

        def body(acc, mb):
            (loss, metrics), grads = grad_fn(params, mb)
            acc = jax.tree.map(
                lambda a, g: a + g.astype(sc.accum_dtype), acc, grads)
            return acc, metrics

        acc, metrics = lax.scan(body, acc0, mbs,
                                unroll=mu if sc.micro_unroll else 1)
        grads = jax.tree.map(lambda a: a / mu, acc)
        metrics = jax.tree.map(lambda m: m.mean(), metrics)
        return grads, metrics

    def train_step(state: TrainState, batch: Dict[str, jax.Array]
                   ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        params = state["params"]
        if sc.microbatches > 1:
            grads, metrics = accumulated(params, batch)
        else:
            grads, metrics = single(params, batch)
        if grad_transform is not None:
            grads = grad_transform(grads)
        grads, gnorm = clip_by_global_norm(grads, sc.clip_norm)
        new_params, new_opt = optimizer.update(params, grads, state["opt_state"],
                                               state["step"])
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        new_state = {"params": new_params, "opt_state": new_opt,
                     "step": state["step"] + 1}
        return new_state, metrics

    return train_step
