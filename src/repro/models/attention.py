"""Grouped-query attention: train (blockwise causal), prefill, decode.

The full-sequence paths use a flash-style two-level ``lax.scan`` (outer
over query blocks, inner over KV blocks with online softmax), so the
S x S score matrix is never materialized — required for the 32k prefill
and the compile-only dry-runs to have sane memory footprints.  On real
TPU the inner loop is replaced by the Pallas flash kernel
(:mod:`repro.kernels.flash_attention`); the jnp path here is its oracle
and the CPU/compile path.

GQA is computed without materializing repeated KV heads: queries are
reshaped to (kv_heads, group, head_dim) and contracted against the
(kv_heads, head_dim) keys directly.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import (
    EMBED,
    HEAD_DIM,
    HEADS,
    KV_HEADS,
    Params,
    apply_rope,
    dense_init,
    larray,
)

_NEG_INF = -1e30


class AttnConfig(NamedTuple):
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 1e4
    causal: bool = True
    q_block: int = 512
    kv_block: int = 512


def head_dim_of(d_model: int, n_heads: int) -> int:
    return d_model // n_heads


def init_attention(key, cfg: AttnConfig, dtype=jnp.float32) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": larray(dense_init(ks[0], (d, h, hd), dtype=dtype), EMBED, HEADS, HEAD_DIM),
        "wk": larray(dense_init(ks[1], (d, kv, hd), dtype=dtype), EMBED, KV_HEADS, HEAD_DIM),
        "wv": larray(dense_init(ks[2], (d, kv, hd), dtype=dtype), EMBED, KV_HEADS, HEAD_DIM),
        "wo": larray(dense_init(ks[3], (h, hd, d), in_axis=0, dtype=dtype), HEADS, HEAD_DIM, EMBED),
    }


def qkv(params: Params, x: jnp.ndarray, positions: jnp.ndarray,
        cfg: AttnConfig) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.rope_theta:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


# ---------------------------------------------------------------------------
# blockwise (flash-style) attention, pure jnp
# ---------------------------------------------------------------------------

def _gqa_scores(q: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """q: (B, Sq, KV, G, Dh), k: (B, Sk, KV, Dh) -> (B, KV, G, Sq, Sk)."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q, k)


def _gqa_out(p: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """p: (B, KV, G, Sq, Sk), v: (B, Sk, KV, Dh) -> (B, Sq, KV, G, Dh)."""
    return jnp.einsum("bhgqk,bkhd->bqhgd", p, v)


def blockwise_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        cfg: AttnConfig,
                        q_offset: int = 0) -> jnp.ndarray:
    """Causal (or full) attention without materializing S x S scores.

    q: (B, Sq, H, Dh); k, v: (B, Sk, KVH, Dh).  ``q_offset`` is the
    absolute position of q[0] relative to k[0] (for prefill
    continuation).  Returns (B, Sq, H, Dh).
    """
    B, Sq, H, Dh = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    qb = min(cfg.q_block, Sq)
    kb = min(cfg.kv_block, Sk)
    # pad to block multiples; padded K positions are masked out via k_pos
    Sq_p, Sk_p = -(-Sq // qb) * qb, -(-Sk // kb) * kb
    if Sq_p != Sq:
        q = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0), (0, 0)))
    if Sk_p != Sk:
        k = jnp.pad(k, ((0, 0), (0, Sk_p - Sk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Sk_p - Sk), (0, 0), (0, 0)))
    nq, nk = Sq_p // qb, Sk_p // kb
    scale = 1.0 / math.sqrt(Dh)

    # GQA via KV repetition to full H rather than a (KV, G) head grouping:
    # the grouped reshape splits the (sharded) head dim into (KV, G)
    # factors that rarely divide the tensor axis, which forces GSPMD to
    # all-gather heads and replicate the attention compute (§Perf: yi-9b
    # prefill useful ratio 0.07).  Repeating KV keeps the contraction on
    # the H-sharded dim; the repeat is a broadcast the compiler fuses.
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    KV_c, G_c = H, 1    # computation proceeds head-diagonal
    qr = q.reshape(B, nq, qb, KV_c, G_c, Dh).astype(jnp.float32) * scale
    kr = k.reshape(B, nk, kb, KV_c, Dh).astype(jnp.float32)
    vr = v.reshape(B, nk, kb, KV_c, Dh).astype(jnp.float32)
    KV, G = KV_c, G_c

    q_pos = q_offset + jnp.arange(Sq_p).reshape(nq, qb)
    # padded keys get position +inf-ish so every mask (causal or not)
    # excludes them
    k_pos_flat = jnp.where(jnp.arange(Sk_p) < Sk, jnp.arange(Sk_p), 2**30)
    k_pos = k_pos_flat.reshape(nk, kb)
    force_mask = cfg.causal or Sk_p != Sk

    def q_step(_, qi):
        qblk, qp = qi                       # (B,qb,KV,G,Dh), (qb,)

        def kv_step(carry, ki):
            m, l, acc = carry
            kblk, vblk, kp = ki
            s = _gqa_scores(qblk, kblk)     # (B,KV,G,qb,kb)
            if force_mask:
                if cfg.causal:
                    mask = qp[:, None] >= kp[None, :]
                else:
                    mask = jnp.broadcast_to(kp[None, :] < 2**30,
                                            (qp.shape[0], kp.shape[0]))
                s = jnp.where(mask[None, None, None], s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vblk)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, qb), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, qb), jnp.float32)
        a0 = jnp.zeros((B, KV, G, qb, Dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kr.transpose(1, 0, 2, 3, 4), vr.transpose(1, 0, 2, 3, 4), k_pos))
        out = acc / jnp.maximum(l, 1e-30)[..., None]    # (B,KV,G,qb,Dh)
        return None, out.transpose(0, 3, 1, 2, 4)        # (B,qb,KV,G,Dh)

    _, outs = jax.lax.scan(q_step, None,
                           (qr.transpose(1, 0, 2, 3, 4, 5), q_pos))
    # outs: (nq, B, qb, KV, G, Dh)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq_p, H, Dh)
    return out[:, :Sq].astype(q.dtype)


def naive_attention(q, k, v, cfg: AttnConfig, q_offset: int = 0):
    """Reference O(S^2)-memory attention (small shapes / tests only)."""
    B, Sq, H, Dh = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    qr = q.reshape(B, Sq, KV, G, Dh).astype(jnp.float32)
    s = _gqa_scores(qr, k.astype(jnp.float32)) / math.sqrt(Dh)
    if cfg.causal:
        qp = q_offset + jnp.arange(Sq)
        mask = qp[:, None] >= jnp.arange(Sk)[None, :]
        s = jnp.where(mask[None, None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = _gqa_out(p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# module-level entry points
# ---------------------------------------------------------------------------

def self_attention(params: Params, x: jnp.ndarray, cfg: AttnConfig,
                   positions: Optional[jnp.ndarray] = None,
                   impl: str = "blockwise", mesh=None) -> jnp.ndarray:
    """Full-sequence causal self-attention (train / prefill compute).

    impl="ring" runs sequence-parallel ring attention over the tensor
    axis (distributed/ring_attention.py): the right choice when heads
    cannot shard over |model| (e.g. starcoder2's 24H/kv2 on a 16-wide
    axis, where head-sharded attention degrades to full replication —
    see EXPERIMENTS.md §Roofline).  Falls back to blockwise when no
    mesh is available or S doesn't divide.
    """
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v = qkv(params, x, positions, cfg)
    if impl == "ring" and mesh is not None and "model" in mesh.shape \
            and S % mesh.shape["model"] == 0:
        from repro.distributed.ring_attention import ring_attention
        out = ring_attention(q, k, v, mesh, axis="model",
                             causal=cfg.causal)
    else:
        fn = blockwise_attention if impl == "blockwise" else naive_attention
        out = fn(q, k, v, cfg)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


def prefill_attention(params: Params, x: jnp.ndarray, cfg: AttnConfig,
                      impl: str = "blockwise", mesh=None):
    """Like self_attention but also returns the (k, v) cache."""
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v = qkv(params, x, positions, cfg)
    if impl == "ring" and mesh is not None and "model" in mesh.shape \
            and S % mesh.shape["model"] == 0:
        from repro.distributed.ring_attention import ring_attention
        out = ring_attention(q, k, v, mesh, axis="model",
                             causal=cfg.causal)
    else:
        fn = blockwise_attention if impl == "blockwise" else naive_attention
        out = fn(q, k, v, cfg)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), (k, v)


def decode_attention(params: Params, x: jnp.ndarray,
                     cache: Tuple[jnp.ndarray, jnp.ndarray],
                     pos: jnp.ndarray, cfg: AttnConfig):
    """Single-token decode: x (B, 1, D); cache k/v (B, S, KVH, Dh);
    pos (B,) current absolute position.  Returns (out, new_cache).

    Both products read the cache in its own dtype and accumulate in
    float32; the softmax is float32 and its p is cast to the cache dtype
    for PV, as OLMo's bf16 path does.  With one query row per head XLA
    lowers each product to a multiply and reduce that streams the cache
    once, with no float32 copy of it.  The new k/v is written at each
    row's own position by a select, which fuses into the copy of the
    cache that the step returns; a scatter there compiles to two more
    passes over the cache."""
    with jax.named_scope("decode_attention"):
        ck, cv = cache
        B, S, KV, Dh = ck.shape
        q, k_new, v_new = qkv(params, x, pos[:, None], cfg)
        at = (jnp.arange(S)[None] == pos[:, None])[..., None, None]
        ck = jnp.where(at, k_new.astype(ck.dtype), ck)
        cv = jnp.where(at, v_new.astype(cv.dtype), cv)
        G = q.shape[2] // KV
        qr = q.reshape(B, KV, G, Dh).astype(ck.dtype)
        s = jnp.einsum("bhgd,bkhd->bhgk", qr, ck,
                       preferred_element_type=jnp.float32) / math.sqrt(Dh)
        valid = jnp.arange(S)[None] <= pos[:, None]          # (B, S)
        s = jnp.where(valid[:, None, None], s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(cv.dtype)
        out = jnp.einsum("bhgk,bkhd->bhgd", p, cv,
                         preferred_element_type=jnp.float32)
        out = out.reshape(B, 1, q.shape[2], Dh).astype(x.dtype)
        return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), (ck, cv)


# ---------------------------------------------------------------------------
# cross attention (VLM image layers, enc-dec decoder)
# ---------------------------------------------------------------------------

def cross_attention(params: Params, x: jnp.ndarray, memory: jnp.ndarray,
                    cfg: AttnConfig) -> jnp.ndarray:
    """x: (B, S, D) queries; memory: (B, M, D) — not causal, no rope on
    memory side (positions encode nothing across modalities)."""
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", memory, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", memory, params["wv"])
    nc_cfg = cfg._replace(causal=False, rope_theta=0.0)
    M = memory.shape[1]
    if S * M <= 4096 * 4096:
        out = naive_attention(q, k, v, nc_cfg)
    else:
        out = blockwise_attention(q, k, v, nc_cfg)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])
