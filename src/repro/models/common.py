"""Shared model components: norms, RoPE, embeddings, init, logical axes.

Every parameter is annotated with *logical* axis names (a tuple of
strings, one per array dim).  The sharding layer
(:mod:`repro.sharding.rules`) maps logical names to mesh axes; models
never mention mesh axes directly, so the same definition runs on a
laptop (1 device), a 16x16 pod, or a multi-pod mesh.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]

# logical axis vocabulary (see repro.sharding.rules for the mesh mapping)
VOCAB = "vocab"          # embedding rows — tensor-parallel
EMBED = "embed"          # d_model — fsdp-sharded
HEADS = "heads"          # attention heads — tensor-parallel
KV_HEADS = "kv_heads"    # kv heads — tensor-parallel
HEAD_DIM = "head_dim"    # per-head dim — replicated
FF = "ff"                # feed-forward hidden — tensor-parallel
EXPERT = "expert"        # MoE expert — expert-parallel
LAYERS = "layers"        # stacked (scanned) layer dim — replicated
CONV = "conv"            # conv kernel taps — replicated
STATE = "state"          # SSM state dim — replicated
INNER = "inner"          # SSM inner dim — tensor-parallel


@dataclasses.dataclass
class LogicalArray:
    """A parameter leaf: value + logical axis names (len == ndim)."""

    value: jnp.ndarray
    axes: Tuple[Optional[str], ...]

    def __post_init__(self) -> None:
        assert len(self.axes) == self.value.ndim, (self.axes, self.value.shape)


def larray(value: jnp.ndarray, *axes: Optional[str]) -> LogicalArray:
    return LogicalArray(value, tuple(axes))


jax.tree_util.register_pytree_node(
    LogicalArray,
    lambda la: ((la.value,), la.axes),
    lambda axes, children: LogicalArray(children[0], axes),
)


def unbox(tree):
    """Strip LogicalArray wrappers -> plain arrays (models compute on this)."""
    return jax.tree_util.tree_map(
        lambda x: x.value if isinstance(x, LogicalArray) else x, tree,
        is_leaf=lambda x: isinstance(x, LogicalArray))


def logical_axes(tree):
    """Matching tree of logical-axes tuples (None leaf -> fully replicated)."""
    return jax.tree_util.tree_map(
        lambda x: x.axes if isinstance(x, LogicalArray) else None, tree,
        is_leaf=lambda x: isinstance(x, LogicalArray))


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def stacked_init(init_fn, key, n: int):
    """Stack ``n`` independent inits along a new leading LAYERS axis,
    preserving per-leaf logical axes (prepends ``layers``)."""
    keys = jax.random.split(key, n)
    boxed0 = init_fn(keys[0])
    leaves0, treedef = jax.tree_util.tree_flatten(
        boxed0, is_leaf=lambda x: isinstance(x, LogicalArray))
    vals = jax.vmap(lambda k: unbox(init_fn(k)))(keys)
    vleaves = jax.tree_util.tree_leaves(vals)
    out = [larray(v, LAYERS, *l.axes) for v, l in zip(vleaves, leaves0)]
    return jax.tree_util.tree_unflatten(treedef, out)


def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    fan_in = shape[in_axis]
    std = fan_in ** -0.5
    return (jax.random.normal(key, shape) * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jnp.ndarray, scale: Optional[jnp.ndarray], eps: float = 1e-6,
            impl: str = "lean"):
    """RMSNorm.

    ``impl="lean"`` computes fp32 *statistics only*: the (…, 1) variance
    is fp32 but every full-width tensor stays in the input dtype — in
    bf16 models this keeps the residual stream, its cotangents, and the
    downstream partial-sum all-reduces bf16 (§Perf: the fp32-upcast
    variant, ``impl="f32"``, dominated the HBM roofline term).
    """
    if impl == "f32":
        dtype = x.dtype
        xf = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        xf = xf * jax.lax.rsqrt(var + eps)
        if scale is not None:
            xf = xf * scale.astype(jnp.float32)
        return xf.astype(dtype)
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    out = x * inv
    if scale is not None:
        out = out * scale.astype(x.dtype)
    return out


def layernorm(x: jnp.ndarray, scale: Optional[jnp.ndarray],
              bias: Optional[jnp.ndarray], eps: float = 1e-5,
            impl: str = "lean"):
    """LayerNorm (see rmsnorm for the lean/f32 distinction)."""
    if impl == "f32":
        dtype = x.dtype
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        xf = (xf - mu) * jax.lax.rsqrt(var + eps)
        if scale is not None:
            xf = xf * scale.astype(jnp.float32)
        if bias is not None:
            xf = xf + bias.astype(jnp.float32)
        return xf.astype(dtype)
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    out = (x - mu.astype(x.dtype)) * inv
    if scale is not None:
        out = out * scale.astype(x.dtype)
    if bias is not None:
        out = out + bias.astype(x.dtype)
    return out


def init_norm(key, d: int, kind: str, dtype=jnp.float32) -> Params:
    """kind: rmsnorm | layernorm | nonparametric (OLMo-1b)."""
    if kind == "rmsnorm":
        return {"scale": larray(jnp.ones((d,), dtype), EMBED)}
    if kind == "layernorm":
        return {"scale": larray(jnp.ones((d,), dtype), EMBED),
                "bias": larray(jnp.zeros((d,), dtype), EMBED)}
    if kind == "nonparametric":
        return {}
    raise ValueError(kind)


def apply_norm(params: Params, x: jnp.ndarray, kind: str,
               impl: str = "lean", eps: Optional[float] = None) -> jnp.ndarray:
    """``eps`` None: the norm's own default."""
    kw = {"impl": impl} if eps is None else {"impl": impl, "eps": eps}
    if kind == "rmsnorm":
        return rmsnorm(x, params["scale"], **kw)
    if kind == "layernorm":
        return layernorm(x, params["scale"], params["bias"], **kw)
    if kind == "nonparametric":
        return layernorm(x, None, None, **kw)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = 1e4) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 1e4) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    freqs = rope_frequencies(x.shape[-1], theta)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# embedding / unembedding (chunked CE lives in train/loss.py)
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d_model: int, dtype=jnp.float32) -> Params:
    return {"table": larray(embed_init(key, (vocab, d_model), dtype),
                            VOCAB, EMBED)}


def embed(params: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    return params["table"][tokens]


def unembed(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    """Logits = x @ table.T (tied weights by default)."""
    return jnp.einsum("...d,vd->...v", x, params["table"])
