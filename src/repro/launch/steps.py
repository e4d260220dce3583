"""jit-able step functions (train / prefill / serve) shared by the real
training driver, the serving loop and the dry-run."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..models import transformer as T
from ..models.common import ModelConfig
from ..optim.adam import adamw_init, adamw_update


def make_train_step(cfg: ModelConfig, *, lr: float = 3e-4,
                    weight_decay: float = 0.1, impl: str = "xla",
                    remat: bool = True):
    def forward(p, batch):
        # the scope sits here, not in loss_fn, so the stage kernel's probe
        # forward is not named bet.forward too; the backward's ops carry
        # transpose(jvp(bet.forward))
        with jax.named_scope("bet.forward"):
            return T.loss_fn(cfg, p, batch, impl=impl, remat=remat)

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            forward, has_aux=True)(params, batch)
        with jax.named_scope("bet.optimizer"):
            params, opt_state = adamw_update(params, grads, opt_state,
                                             lr=lr, weight_decay=weight_decay)
            gnorm = jnp.sqrt(sum(
                jnp.sum(g.astype(jnp.float32) ** 2)
                for g in jax.tree_util.tree_leaves(grads)))
        metrics = dict(metrics, loss=loss, grad_norm=gnorm)
        return params, opt_state, metrics

    return train_step


def init_opt_state(params):
    return adamw_init(params)


def opt_state_specs(params_shape):
    """ShapeDtypeStructs of the Adam state mirroring an abstract params tree."""
    return jax.eval_shape(adamw_init, params_shape)


def make_prefill_step(cfg: ModelConfig, *, impl: str = "xla",
                      cache_len: int | None = None):
    def prefill_fn(params, batch):
        return T.prefill_step(cfg, params, batch, impl=impl,
                              cache_len=cache_len)
    return prefill_fn


def make_serve_step(cfg: ModelConfig):
    def serve_step(params, cache, batch):
        return T.decode_step(cfg, params, cache, batch)
    return serve_step
