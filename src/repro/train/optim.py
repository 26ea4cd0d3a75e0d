"""AdamW + global-norm clipping + cosine schedule, built in-house.

Optimizer state is a pytree congruent with the params, so the sharding
layer shards first/second moments exactly like their parameters —
params are already FSDP-sharded over the data axis (embed -> data),
which makes this ZeRO-style: no device holds a full optimizer replica.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class OptConfig:
    lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0


class OptState(NamedTuple):
    mu: Any          # first moment  (tree like params)
    nu: Any          # second moment (tree like params)
    count: jnp.ndarray


def lr_schedule(cfg: OptConfig, step: jnp.ndarray) -> jnp.ndarray:
    step = step.astype(jnp.float32)
    warm = cfg.lr * step / max(cfg.warmup_steps, 1)
    prog = jnp.clip((step - cfg.warmup_steps)
                    / max(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
    cos = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 * (
        1 + jnp.cos(jnp.pi * prog))
    return jnp.where(step < cfg.warmup_steps, warm, cfg.lr * cos)


def init_opt_state(params: Any) -> OptState:
    zeros = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return OptState(mu=zeros,
                    nu=jax.tree_util.tree_map(jnp.copy, zeros),
                    count=jnp.zeros((), jnp.int32))


def global_norm(tree: Any) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def decay_mask(boxed_params: Any) -> Any:
    """Per leaf of the (boxed) parameters, whether weight decay applies:
    to matrix-like leaves only, each layer's own axes counted and not the
    ``layers`` axis a stack adds, so stacked norm scales, biases and
    per-head vectors (Mamba-2's ``A_log``, ``D``, ``dt_bias``) are not
    decayed.  A leaf with no logical axes counts every axis."""
    from repro.models.common import LAYERS, LogicalArray

    def matrix_like(x):
        if isinstance(x, LogicalArray):
            return sum(a != LAYERS for a in x.axes) >= 2
        return x.ndim >= 2

    return jax.tree_util.tree_map(
        matrix_like, boxed_params,
        is_leaf=lambda x: isinstance(x, LogicalArray))


def adamw_update(cfg: OptConfig, grads: Any, state: OptState,
                 params: Any, decay: Any
                 ) -> Tuple[Any, OptState, Dict[str, jnp.ndarray]]:
    """Returns (new_params, new_state, metrics).  ``decay``: a tree like
    ``params`` of bools (``decay_mask``) saying which leaves weight decay
    applies to."""
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-12))
    count = state.count + 1
    lr = lr_schedule(cfg, count)
    b1c = 1 - cfg.b1 ** count.astype(jnp.float32)
    b2c = 1 - cfg.b2 ** count.astype(jnp.float32)

    def upd(p, g, m, v, d):
        g = g.astype(jnp.float32) * scale
        m = cfg.b1 * m + (1 - cfg.b1) * g
        v = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g)
        mhat = m / b1c
        vhat = v / b2c
        step = mhat / (jnp.sqrt(vhat) + cfg.eps)
        # decoupled weight decay on matrix-like params only
        if d:
            step = step + cfg.weight_decay * p.astype(jnp.float32)
        new_p = p.astype(jnp.float32) - lr * step
        return new_p.astype(p.dtype), m, v

    flat_p, tdef = jax.tree_util.tree_flatten(params)
    flat_g = jax.tree_util.tree_leaves(grads)
    flat_m = jax.tree_util.tree_leaves(state.mu)
    flat_v = jax.tree_util.tree_leaves(state.nu)
    flat_d = jax.tree_util.tree_leaves(decay)
    out = [upd(*a) for a in zip(flat_p, flat_g, flat_m, flat_v, flat_d)]
    new_params = jax.tree_util.tree_unflatten(tdef, [o[0] for o in out])
    new_mu = jax.tree_util.tree_unflatten(tdef, [o[1] for o in out])
    new_nu = jax.tree_util.tree_unflatten(tdef, [o[2] for o in out])
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_params, OptState(new_mu, new_nu, count), metrics
