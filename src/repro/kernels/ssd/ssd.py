"""Pallas TPU kernel: Mamba-2 SSD chunked scan.

One grid point computes one (batch, head, chunk) cell: the intra-chunk
quadratic term (decay-masked C·Bᵀ attention over the chunk) plus the
inter-chunk contribution from the running state.  The state (N, P)
lives in VMEM **scratch carried across grid steps**: the chunk axis is
the last (sequential) grid dimension, so the scratch behaves as the
`lax.scan` carry of the jnp reference (`repro.models.mamba2.ssd_chunked`
— the oracle) without ever round-tripping through HBM.

Tile geometry: Q×Q decay/score tiles (Q=chunk, default 128) and Q×P /
Q×N operand tiles are MXU-aligned for P=64..128, N=64..128; the per-step
working set (~4·Q² + 4·Q·(N+P) fp32 at Q=128) is well under VMEM.

This replaces the dominant intra-chunk traffic of the jnp path: the
(Q,Q) decay tensor never leaves VMEM (on the jnp path it is an HBM
round-trip per chunk per head — the §Perf mamba2 analysis).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, o_ref, state, *,
            Q: int, N: int, P: int):
    h = pl.program_id(1)
    ci = pl.program_id(2)                     # chunk index (sequential)

    @pl.when(ci == 0)
    def _reset():
        state[...] = jnp.zeros((N, P), jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)                 # (Q, P)
    dt_row = dt_ref[0, 0].astype(jnp.float32)           # (1, Q)
    A = a_ref[h]                                        # scalar (negative)
    Bm = b_ref[0, 0].astype(jnp.float32)                # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)                # (Q, N)

    # cumulative decay along the chunk, as a column and as a row, by
    # masked reductions (no cumsum or transpose on the chip)
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    dt = jnp.sum(jnp.where(row == col, dt_row, 0.0), axis=1,
                 keepdims=True)                         # (Q, 1)
    dA_row = dt_row * A
    cum = jnp.sum(jnp.where(col <= row, dA_row, 0.0), axis=1,
                  keepdims=True)                        # (Q, 1)
    cum_row = jnp.sum(jnp.where(row <= col, dt * A, 0.0), axis=0,
                      keepdims=True)                    # (1, Q)
    total = jnp.sum(dA_row, axis=1, keepdims=True)      # (1, 1)
    # intra-chunk decay matrix, causal-masked
    decay = jnp.where(row >= col, jnp.exp(cum - cum_row), 0.0)
    xdt = x * dt                                        # (Q, P)
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))  # (Q, Q)
    y_intra = jax.lax.dot(cb * decay, xdt)              # (Q, P)
    # inter-chunk from carried state
    s_prev = state[...]
    y_inter = jax.lax.dot(Cm * jnp.exp(cum), s_prev)
    # state update
    sdecay = jnp.exp(total - cum)                       # (Q, 1)
    s_new = s_prev * jnp.exp(total) + jax.lax.dot_general(
        Bm * sdecay, xdt, (((0,), (0,)), ((), ())))     # (N, P)
    state[...] = s_new
    o_ref[...] = (y_intra + y_inter).reshape(1, 1, Q, P).astype(o_ref.dtype)


def ssd_pallas(xh: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
               Bm: jnp.ndarray, Cm: jnp.ndarray, chunk: int = 128,
               interpret: bool = False) -> jnp.ndarray:
    """SSD forward.  xh: (B, L, H, P); dt: (B, L, H) post-softplus;
    A: (H,) negative; Bm, Cm: (B, L, G, N) with G == 1 (broadcast heads).

    Returns y: (B, L, H, P).  L % chunk == 0.
    """
    B, L, H, P = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert G == 1, "kernel broadcasts one B/C group over heads"
    assert L % chunk == 0
    nc, Q = L // chunk, chunk
    # heads ahead of (positions, features): every tile's last two dims
    # are (Q, P), (1, Q) or (Q, N)
    xq = xh.transpose(0, 2, 1, 3)                       # (B, H, L, P)
    dtq = dt.transpose(0, 2, 1)[:, :, None, :]          # (B, H, 1, L)
    Bq = Bm.reshape(B, nc, Q, N)
    Cq = Cm.reshape(B, nc, Q, N)
    kernel = functools.partial(_kernel, Q=Q, N=N, P=P)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nc),                    # chunk LAST: sequential carry
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, L, P), xh.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xq, dtq, A.astype(jnp.float32), Bq, Cq)
    return out.transpose(0, 2, 1, 3)
