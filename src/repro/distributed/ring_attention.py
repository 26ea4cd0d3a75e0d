"""Ring attention: sequence-parallel causal attention via collective-permute.

This is the paper's shuffle at *mesh* granularity (DESIGN.md §2): on a
warp, ``shfl.up`` hands a register to the neighbouring lane; on a TPU
mesh, ``ppermute`` hands a KV block to the neighbouring chip over ICI.
Both replace a redundant gather (global-memory re-load / KV all-gather)
with nearest-neighbour communication whose legality was proven
statically — there, by the symbolic emulator; here, by the blockwise
softmax algebra.

q, k, v arrive sequence-sharded over ``axis``; each of the ``tp`` ring
steps computes the partial attention of the local q block against the
currently-resident kv block (online-softmax merge), then rotates the kv
block one hop around the ring.  Peak memory is O(S_local^2) per chip;
the KV all-gather (and its |model| x memory blowup) never happens;
compute and ppermute overlap in steady state on real hardware.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

_NEG_INF = -1e30


def _partial_attn(q, k, v, q_pos, k_pos, causal):
    """Blockwise partial attention with explicit positions.

    q: (B, Sq, KV, G, Dh); k, v: (B, Sk, KV, Dh).
    Returns (scores-max m, normalizer l, weighted accum acc).
    """
    Dh = q.shape[-1]
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(Dh)
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhgqk,bkhd->bhgqd", p, v.astype(jnp.float32))
    return m, l, acc


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   mesh, axis: str = "model", causal: bool = True):
    """q: (B, S, H, Dh); k, v: (B, S, KV, Dh), all sequence-shardable by
    ``axis``.  Returns (B, S, H, Dh) attention output."""
    B, S, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    tp = mesh.shape[axis]
    assert S % tp == 0

    def local(q, k, v):
        idx = jax.lax.axis_index(axis)
        Sl = q.shape[1]
        qg = q.reshape(B, Sl, KV, G, Dh)
        q_pos = idx * Sl + jnp.arange(Sl)
        perm = [(j, (j + 1) % tp) for j in range(tp)]

        def step(carry, i):
            m, l, acc, kb, vb = carry
            src = (idx - i) % tp                       # owner of resident kv
            k_pos = src * Sl + jnp.arange(Sl)
            m2, l2, acc2 = _partial_attn(qg, kb, vb, q_pos, k_pos, causal)
            m_new = jnp.maximum(m, m2)
            c1 = jnp.exp(m - m_new)
            c2 = jnp.exp(m2 - m_new)
            l_new = l * c1 + l2 * c2
            acc_new = acc * c1[..., None] + acc2 * c2[..., None]
            kb = jax.lax.ppermute(kb, axis, perm)      # the mesh "shuffle"
            vb = jax.lax.ppermute(vb, axis, perm)
            return (m_new, l_new, acc_new, kb, vb), None

        m0 = jnp.full((B, KV, G, Sl), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, Sl), jnp.float32)
        a0 = jnp.zeros((B, KV, G, Sl, Dh), jnp.float32)
        (m, l, acc, _, _), _ = jax.lax.scan(
            step, (m0, l0, a0, k, v), jnp.arange(tp))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sl, H, Dh)
        return out.astype(q.dtype)

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(None, axis), P(None, axis), P(None, axis)),
        out_specs=P(None, axis), check_vma=False)(q, k, v)
