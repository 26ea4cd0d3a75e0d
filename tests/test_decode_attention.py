"""Single-token decode attention (``decode_attention``): the output against
float32 attention over the same cache prefix, the written cache against
the one-hot blend it replaced, and a bf16 cache that is never upcast."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import unbox
from repro.models.attention import (
    AttnConfig, decode_attention, init_attention, naive_attention, qkv)

B, S, DH = 4, 32, 16


def _cfg(heads: int, kv_heads: int) -> AttnConfig:
    return AttnConfig(d_model=32, n_heads=heads, n_kv_heads=kv_heads,
                      head_dim=DH)


def _inputs(cfg: AttnConfig, dtype, seed: int = 0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    params = jax.tree.map(lambda a: a.astype(dtype),
                          unbox(init_attention(k[0], cfg)))
    x = jax.random.normal(k[1], (B, 1, cfg.d_model), dtype)
    shape = (B, S, cfg.n_kv_heads, DH)
    cache = (jax.random.normal(k[2], shape, dtype),
             jax.random.normal(k[3], shape, dtype))
    return params, x, cache


def _onehot_decode_attention(params, x, cache, pos, cfg):
    """The formula ``decode_attention`` had before it read the cache in
    its own dtype: a one-hot blend for the write, the whole cache cast to
    float32 for both products."""
    ck, cv = cache
    _, _, KV, Dh = ck.shape
    q, k_new, v_new = qkv(params, x, pos[:, None], cfg)
    onehot = jax.nn.one_hot(pos, S, dtype=ck.dtype)[..., None, None]
    ck = ck * (1 - onehot) + onehot * k_new
    cv = cv * (1 - onehot) + onehot * v_new
    G = q.shape[2] // KV
    qr = q.reshape(B, 1, KV, G, Dh).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qr, ck.astype(jnp.float32))
    s = s / math.sqrt(Dh)
    valid = jnp.arange(S)[None] <= pos[:, None]
    s = jnp.where(valid[:, None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, cv.astype(jnp.float32))
    out = out.reshape(B, 1, q.shape[2], Dh).astype(x.dtype)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), (ck, cv)


POSITIONS = {"equal": [9, 9, 9, 9], "different": [3, 17, 0, 31],
             "first": [0, 0, 0, 0], "last": [S - 1] * 4}
CASES = [  # (heads, kv_heads, positions, cache dtype)
    (4, 4, "equal", jnp.bfloat16),
    (4, 4, "different", jnp.bfloat16),
    (4, 2, "equal", jnp.bfloat16),
    (4, 2, "different", jnp.bfloat16),
    (4, 4, "first", jnp.bfloat16),
    (4, 2, "last", jnp.bfloat16),
    (4, 4, "different", jnp.float32),
    (4, 2, "different", jnp.float32),
]


@pytest.mark.parametrize("heads,kv_heads,where,dtype", CASES)
def test_decode_attention(heads, kv_heads, where, dtype):
    cfg = _cfg(heads, kv_heads)
    params, x, cache = _inputs(cfg, dtype)
    pos = jnp.asarray(POSITIONS[where], jnp.int32)
    out, (ck, cv) = jax.jit(decode_attention, static_argnums=4)(
        params, x, cache, pos, cfg)
    want_out, (want_k, want_v) = jax.jit(
        _onehot_decode_attention, static_argnums=4)(params, x, cache, pos, cfg)

    # the written cache is the one-hot blend's, bit for bit: the new k/v
    # at each row's own position and every other position untouched
    assert ck.dtype == cache[0].dtype and cv.dtype == cache[1].dtype
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(want_k))
    np.testing.assert_array_equal(np.asarray(cv), np.asarray(want_v))
    rows = np.arange(B)
    _, k_new, v_new = jax.jit(qkv, static_argnums=3)(
        params, x, pos[:, None], cfg)
    np.testing.assert_array_equal(np.asarray(ck)[rows, np.asarray(pos)],
                                  np.asarray(k_new[:, 0]))
    np.testing.assert_array_equal(np.asarray(cv)[rows, np.asarray(pos)],
                                  np.asarray(v_new[:, 0]))

    if dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                                   rtol=1e-6, atol=1e-6)
        return
    # float32 attention over the same cache prefix, row by row, projected
    # in float32; the program rounds p, the attention output and the
    # projection to bf16, each a relative 2**-8
    q, _, _ = qkv(params, x, pos[:, None], cfg)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    ref = []
    for b in range(B):
        n = int(pos[b]) + 1
        o = naive_attention(f32(q[b:b + 1]), f32(ck[b:b + 1, :n]),
                            f32(cv[b:b + 1, :n]), cfg, q_offset=n - 1)
        ref.append(jnp.einsum("bshk,hkd->bsd", o, f32(params["wo"])))
    ref = np.asarray(jnp.concatenate(ref))
    scale = float(np.max(np.abs(ref)))
    np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                               rtol=3 * 2.0 ** -8, atol=3 * 2.0 ** -8 * scale)


def _converts(jaxpr):
    """Every convert_element_type of a jaxpr and of the jaxprs inside it,
    as (operand shape, new dtype)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "convert_element_type":
            yield eqn.invars[0].aval.shape, eqn.params["new_dtype"]
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _converts(inner)


@pytest.mark.parametrize("kv_heads", [4, 2])
def test_decode_attention_keeps_the_cache_dtype(kv_heads):
    """No float32 copy of a bf16 cache: nothing the size of the cache is
    converted."""
    cfg = _cfg(4, kv_heads)
    params, x, cache = _inputs(cfg, jnp.bfloat16)
    pos = jnp.asarray(POSITIONS["different"], jnp.int32)
    closed = jax.make_jaxpr(decode_attention, static_argnums=4)(
        params, x, cache, pos, cfg)
    size = cache[0].size
    converted = [(shape, dt) for shape, dt in _converts(closed.jaxpr)
                 if math.prod(shape) >= size]
    assert not converted, converted
