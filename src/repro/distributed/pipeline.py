"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

Microbatches flow through stages via ``ppermute`` (the inter-chip
shuffle); each device applies its stage's parameters.  The schedule is
the classic (n_micro + n_stages - 1)-step wavefront; bubbles shrink as
n_micro grows.  Used as an optional parallelism layer for deep models
(deepseek-67b 95L, llama-vision 100L) when meshes grow a ``stage`` axis;
validated against sequential application in tests/test_distributed.py.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params: Any, x: jnp.ndarray,
                   mesh, axis: str = "stage"):
    """Apply ``n_stages`` stages to ``n_micro`` microbatches.

    stage_fn(params_i, x) -> x        (one stage's computation)
    stage_params: tree with leading dim = n_stages (sharded over axis)
    x: (n_micro, micro_batch, ...) microbatched input (replicated)

    Returns (n_micro, micro_batch, ...) outputs after all stages.
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    T = n_micro + n_stages - 1
    perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]

    def local(params, x):
        idx = jax.lax.axis_index(axis)
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        buf = jnp.zeros_like(x[0])                 # resident activation
        outs = jnp.zeros_like(x)

        def step(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if in range)
            feed = x[jnp.clip(t, 0, n_micro - 1)]
            cur = jnp.where(idx == 0, feed, buf)
            y = stage_fn(params, cur)
            # last stage emits microbatch (t - n_stages + 1)
            out_idx = t - (n_stages - 1)
            valid = (idx == n_stages - 1) & (out_idx >= 0) & (out_idx < n_micro)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(out_idx, 0, n_micro - 1), 0),
                lambda o: o, outs)
            # rotate activations downstream (the wavefront shuffle)
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(step, (buf, outs), jnp.arange(T))
        # only the last stage holds real outputs; broadcast them
        outs = jax.lax.psum(
            jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    pspec = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    return shard_map(local, mesh=mesh,
                         in_specs=(pspec, P()), out_specs=P(),
                         check_vma=False)(stage_params, x)
