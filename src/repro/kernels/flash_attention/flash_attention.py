"""Pallas TPU flash attention (causal, GQA) — the train/prefill hot spot.

Blockwise online-softmax attention: grid over (batch, kv-head, q-block);
the kernel loops over KV blocks with ``jax.lax.fori_loop``, copying each
K/V block HBM -> VMEM with an explicit DMA and keeping the running max /
normalizer / accumulator on chip — the S x S score matrix never exists.
Causal blocks beyond the diagonal are skipped by bounding the loop trip
count at the q-block's diagonal (no masked-out FLOPs at block
granularity; the diagonal block is element-masked).

Block shapes default to (128, 512): the q/kv tiles and the (128, 512)
score tile are MXU-aligned (multiples of 8x128 VREGs), and the working
set per step — q (128, Dh) + k/v (512, Dh) + scores (128, 512) fp32 —
fits VMEM comfortably for Dh <= 256.

Oracle: :func:`repro.models.attention.naive_attention` (and the
blockwise jnp path); validated in interpret mode over shape/dtype sweeps
in tests/test_kernels.py and compiled for the TPU in
tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, *, Bq: int,
                  Bk: int, G: int, Dh: int, Sk: int, causal: bool):
    b = pl.program_id(0)
    h = pl.program_id(1)          # kv head
    qi = pl.program_id(2)
    q0 = qi * Bq
    # q tile: (G, Bq, Dh) -> (G*Bq, Dh), row r = (group r // Bq, r % Bq)
    q = q_ref[0, 0].reshape(G * Bq, Dh).astype(jnp.float32) * (Dh ** -0.5)

    nk_total = Sk // Bk
    if causal:
        # process KV blocks covering positions <= q0 + Bq - 1
        nk = jnp.minimum((q0 + Bq + Bk - 1) // Bk, nk_total)
    else:
        nk = nk_total

    def body(ki, carry):
        m, l, acc = carry
        k0 = ki * Bk
        if Bk % 8 == 0:
            k0 = pl.multiple_of(k0, 8)
        # HBM -> VMEM: this KV block of head h
        ck = pltpu.make_async_copy(k_hbm.at[b, h, pl.ds(k0, Bk)], kbuf,
                                   sem.at[0])
        cv = pltpu.make_async_copy(v_hbm.at[b, h, pl.ds(k0, Bk)], vbuf,
                                   sem.at[1])
        ck.start()
        cv.start()
        ck.wait()
        cv.wait()
        k = kbuf[...].astype(jnp.float32)                          # (Bk, Dh)
        v = vbuf[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # (G*Bq, Bk)
        if causal:
            qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (G, Bq, Bk), 1)
            kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (G, Bq, Bk), 2)
            s = jnp.where(qpos >= kpos, s.reshape(G, Bq, Bk),
                          _NEG_INF).reshape(G * Bq, Bk)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + jax.lax.dot(p, v)
        return m_new, l_new, acc_new

    m0 = jnp.full((G * Bq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((G * Bq, 1), jnp.float32)
    a0 = jnp.zeros((G * Bq, Dh), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nk, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)
    o_ref[...] = out.reshape(1, 1, G, Bq, Dh).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 512, interpret: bool = False) -> jnp.ndarray:
    """q: (B, Sq, H, Dh); k, v: (B, Sk, KV, Dh); H % KV == 0.

    Returns (B, Sq, H, Dh).  Sq/Sk are padded internally to block
    multiples (padded keys masked, padded queries dropped).
    """
    B, Sq, H, Dh = q.shape
    _, Sk, KV, _ = k.shape
    assert H % KV == 0
    G = H // KV
    Bq = min(block_q, Sq)
    Bk = min(block_k, Sk)
    Sq_p, Sk_p = -(-Sq // Bq) * Bq, -(-Sk // Bk) * Bk
    if Sq_p != Sq:
        q = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0), (0, 0)))
    if Sk_p != Sk:
        # padded keys must never win the softmax: causal masking handles
        # them for causal=True (they sit at positions >= Sk >= any q);
        # for causal=False we bound the kv loop to real blocks only by
        # requiring divisibility instead.
        assert causal, "non-causal flash requires Sk % block_k == 0"
        k = jnp.pad(k, ((0, 0), (0, Sk_p - Sk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Sk_p - Sk), (0, 0), (0, 0)))
    # head-major layouts: every tile's last two dims are (positions, Dh)
    qg = q.reshape(B, Sq_p, KV, G, Dh).transpose(0, 2, 3, 1, 4)
    kh = k.transpose(0, 2, 1, 3)                        # (B, KV, Sk_p, Dh)
    vh = v.transpose(0, 2, 1, 3)
    kernel = functools.partial(_flash_kernel, Bq=Bq, Bk=Bk, G=G, Dh=Dh,
                               Sk=Sk_p, causal=causal)
    blk = pl.BlockSpec((1, 1, G, Bq, Dh), lambda b, h, qi: (b, h, 0, qi, 0))
    out = pl.pallas_call(
        kernel,
        grid=(B, KV, Sq_p // Bq),
        in_specs=[blk, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, Sq_p, Dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((Bk, Dh), k.dtype),
                        pltpu.VMEM((Bk, Dh), v.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
        interpret=interpret,
    )(qg, kh, vh)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq_p, H, Dh)
    return out[:, :Sq]
