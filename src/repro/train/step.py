"""Train-step factory: loss -> grad -> (optional compression) -> AdamW.

``make_train_step(model, opt_cfg, ...)`` returns a pure function
``(params, opt_state, batch) -> (params, opt_state, metrics)`` suitable
for ``jax.jit`` with explicit in/out shardings (see launch/dryrun.py and
launch/train.py).  Optional features:

* ``accum_steps`` — microbatch gradient accumulation via ``lax.scan``
  (batch is split along dim 0).
* ``compress_pod_grads`` — int8 + error-feedback gradient compression
  for the cross-pod all-reduce (distributed/compression.py): the `pod`
  axis is pure DP over slow inter-pod links, the classic place for
  compression.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .optim import OptConfig, OptState, adamw_update, decay_mask


def make_train_step(model, opt_cfg: OptConfig,
                    accum_steps: int = 1,
                    compress_pod_grads: bool = False,
                    mesh=None) -> Callable:
    loss_fn = lambda p, b: model.loss(p, b)
    decay = decay_mask(jax.eval_shape(model.init, jax.random.PRNGKey(0)))

    def compute_grads(params, batch):
        if accum_steps == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            return loss, metrics, grads

        def micro(batch_i):
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch_i)
            return loss, metrics, grads

        def split(x):
            return x.reshape((accum_steps, x.shape[0] // accum_steps)
                             + x.shape[1:])

        micro_batches = jax.tree_util.tree_map(split, batch)

        def body(carry, batch_i):
            loss_acc, grads_acc = carry
            loss, metrics, grads = micro(batch_i)
            grads_acc = jax.tree_util.tree_map(jnp.add, grads_acc, grads)
            return (loss_acc + loss, grads_acc), metrics

        zero_grads = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss_sum, grads), metrics = jax.lax.scan(
            body, (jnp.float32(0), zero_grads), micro_batches)
        grads = jax.tree_util.tree_map(lambda g: g / accum_steps, grads)
        metrics = jax.tree_util.tree_map(lambda m: m[-1], metrics)
        return loss_sum / accum_steps, metrics, grads

    def train_step(params, opt_state: OptState, batch):
        loss, metrics, grads = compute_grads(params, batch)
        if compress_pod_grads and mesh is not None and "pod" in mesh.shape:
            from repro.distributed.compression import pod_compressed_mean
            grads = pod_compressed_mean(grads, mesh)
        params, opt_state, opt_metrics = adamw_update(
            opt_cfg, grads, opt_state, params, decay)
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return train_step
