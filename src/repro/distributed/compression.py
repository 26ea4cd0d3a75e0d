"""Gradient compression for the cross-pod data-parallel reduce.

The ``pod`` axis is pure DP over the slowest links (inter-pod DCN/ICI),
the canonical target for compression.  Two schemes:

``pod_compressed_mean``
    stateless int8 quantization (per-leaf max-abs scale) + all_gather
    over ``pod`` + local dequant-mean: 4x less cross-pod traffic than an
    fp32 ring all-reduce, bias-free in expectation when combined with
    error feedback.

``ef_compressed_mean``
    the same with *error feedback*: the quantization residual is carried
    to the next step and added before quantizing, which provably
    restores convergence for contractive compressors.  Residual state is
    a params-shaped tree the caller threads through training state.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def _quantize(g: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _mean_over_pod(q: jnp.ndarray, scale: jnp.ndarray, axis: str):
    qg = jax.lax.all_gather(q, axis)            # (pods, ...)
    sg = jax.lax.all_gather(scale, axis)        # (pods,)
    deq = qg.astype(jnp.float32) * sg.reshape((-1,) + (1,) * q.ndim)
    return jnp.mean(deq, axis=0)


def pod_compressed_mean(grads: Any, mesh, axis: str = "pod") -> Any:
    """Mean-reduce grads over the pod axis with int8 on the wire."""

    def leaf_fn(g):
        q, s = _quantize(g.astype(jnp.float32))
        return _mean_over_pod(q, s, axis)

    def local(grads):
        return jax.tree_util.tree_map(leaf_fn, grads)

    spec = jax.tree_util.tree_map(lambda _: P(), grads)
    return shard_map(local, mesh=mesh, in_specs=(spec,),
                         out_specs=spec, check_vma=False)(grads)


def ef_compressed_mean(grads: Any, residual: Any, mesh,
                       axis: str = "pod") -> Tuple[Any, Any]:
    """Error-feedback variant: returns (mean grads, new residual)."""

    def leaf_fn(g, r):
        corrected = g.astype(jnp.float32) + r
        q, s = _quantize(corrected)
        sent = q.astype(jnp.float32) * s
        new_r = corrected - sent
        return _mean_over_pod(q, s, axis), new_r

    def local(grads, residual):
        flat_g, tdef = jax.tree_util.tree_flatten(grads)
        flat_r = jax.tree_util.tree_leaves(residual)
        out = [leaf_fn(g, r) for g, r in zip(flat_g, flat_r)]
        means = jax.tree_util.tree_unflatten(tdef, [o[0] for o in out])
        resid = jax.tree_util.tree_unflatten(tdef, [o[1] for o in out])
        return means, resid

    spec = jax.tree_util.tree_map(lambda _: P(), grads)
    return shard_map(local, mesh=mesh, in_specs=(spec, spec),
                         out_specs=(spec, spec), check_vma=False)(
                             grads, residual)
