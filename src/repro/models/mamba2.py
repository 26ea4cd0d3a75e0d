"""Mamba-2 (SSD, state-space duality) block — pure JAX.

Implements the chunked SSD algorithm of Dao & Gu (arXiv:2405.21060):
intra-chunk quadratic attention-like term + inter-chunk state recurrence
carried by ``lax.scan`` — O(L * Q) compute with chunk size Q, and an O(1)
recurrent ``decode_step`` used for the 32k/500k decode shapes.

The depthwise causal conv1d over (x, B, C) is a width-4 *stencil along
the sequence* — exactly the paper's shuffle pattern (taps i-3..i of the
same array).  The jnp path here (`causal_conv1d_ref`) is the oracle; the
Pallas kernel in :mod:`repro.kernels.conv1d` serves taps from a single
staged tile with shifted slices, as selected by the PTXASW delta
analysis (see DESIGN.md §2 and tests/test_kernels.py).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import (
    CONV,
    EMBED,
    HEADS,
    INNER,
    Params,
    STATE,
    dense_init,
    larray,
    rmsnorm,
)


class SSMConfig(NamedTuple):
    d_model: int
    d_state: int
    head_dim: int = 64
    expand: int = 2
    n_groups: int = 1
    conv_width: int = 4
    chunk: int = 256
    dt_min: float = 1e-3
    dt_max: float = 1e-1
    norm_eps: float = 1e-6      # the gated RMSNorm's
    mm_dtype: str = "float32"   # float32 | compute: dtype of the SSD
                                # intra-chunk matmul operands (cum/decay
                                # math stays fp32) — §Perf hillclimb

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state


def init_mamba2(key, cfg: SSMConfig, dtype=jnp.float32) -> Params:
    d, di, ng, ns = cfg.d_model, cfg.d_inner, cfg.n_groups, cfg.d_state
    H = cfg.n_heads
    ks = jax.random.split(key, 6)
    d_in_proj = 2 * di + 2 * ng * ns + H     # z, x, B, C, dt
    dt = jnp.exp(jax.random.uniform(ks[3], (H,))
                 * (math.log(cfg.dt_max) - math.log(cfg.dt_min))
                 + math.log(cfg.dt_min))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))   # inverse softplus
    return {
        "w_in": larray(dense_init(ks[0], (d, d_in_proj), dtype=dtype),
                       EMBED, INNER),
        "conv_w": larray(dense_init(ks[1], (cfg.conv_width, cfg.conv_dim),
                                    dtype=dtype) * 0.5, CONV, INNER),
        "conv_b": larray(jnp.zeros((cfg.conv_dim,), dtype), INNER),
        "a_log": larray(jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
                        HEADS),
        "dt_bias": larray(dt_bias.astype(jnp.float32), HEADS),
        "d_skip": larray(jnp.ones((H,), jnp.float32), HEADS),
        "norm_scale": larray(jnp.ones((di,), dtype), INNER),
        "w_out": larray(dense_init(ks[2], (di, d), dtype=dtype), INNER, EMBED),
    }


# ---------------------------------------------------------------------------
# causal depthwise conv1d (the paper-relevant stencil)
# ---------------------------------------------------------------------------

def causal_conv1d_ref(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                      state: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """x: (B, L, C); w: (W, C); b: (C).  Left-pads with ``state``
    ((B, W-1, C), zeros if None).  One shifted-slice per tap — the jnp
    oracle of the shuffle-reuse Pallas kernel."""
    W = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    L = x.shape[1]
    acc = b
    for t in range(W):
        acc = acc + xp[:, t:t + L] * w[t]
    return jax.nn.silu(acc)


def conv1d_step(x_t: jnp.ndarray, conv_state: jnp.ndarray,
                w: jnp.ndarray, b: jnp.ndarray):
    """Decode: x_t (B, C); conv_state (B, W-1, C) last inputs."""
    window = jnp.concatenate([conv_state, x_t[:, None]], axis=1)  # (B,W,C)
    y = jnp.einsum("bwc,wc->bc", window, w) + b
    return jax.nn.silu(y), window[:, 1:]


# ---------------------------------------------------------------------------
# chunked SSD scan
# ---------------------------------------------------------------------------

def _split_proj(params: Params, x: jnp.ndarray, cfg: SSMConfig):
    di, ng, ns, H = cfg.d_inner, cfg.n_groups, cfg.d_state, cfg.n_heads
    proj = jnp.einsum("...d,dk->...k", x, params["w_in"])
    z, xin, Bc, Cc, dt = jnp.split(
        proj, [di, 2 * di, 2 * di + ng * ns, 2 * di + 2 * ng * ns], axis=-1)
    return z, xin, Bc, Cc, dt


def ssd_chunked(xh: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                Bm: jnp.ndarray, Cm: jnp.ndarray, chunk: int,
                init_state: Optional[jnp.ndarray] = None,
                mm_dtype: str = "float32"):
    """SSD core.  xh: (B, L, H, P); dt: (B, L, H) (post-softplus);
    A: (H,) negative decay rates; Bm, Cm: (B, L, G, N).

    ``mm_dtype="compute"`` keeps the intra-chunk matmul operands (the
    (B,Q,Q,H) decay/score tensors — the traffic hot spot) in the input
    dtype with fp32 accumulation; the cumulative-decay math is always
    fp32.  Returns (y: (B, L, H, P), final_state: (B, H, N, P)).
    """
    Bsz, L, H, P = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert L % chunk == 0, (L, chunk)
    nc, Q = L // chunk, chunk
    rep = H // G
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    mm = xh.dtype if mm_dtype == "compute" else jnp.float32
    # fp32 accumulation for low-precision operands (MXU-native on TPU).
    # The CPU runtime cannot *execute* BF16xBF16=F32 dots (DotThunk
    # limitation), so smoke runs fall back to same-dtype accumulation;
    # compile-only dry-runs are unaffected either way.
    if mm == jnp.bfloat16 and jax.default_backend() == "cpu":
        acc32 = {}
    else:
        acc32 = dict(preferred_element_type=jnp.float32)

    # scanned-chunk layout: leading axis = chunk index
    xq = xh.reshape(Bsz, nc, Q, H, P).transpose(1, 0, 2, 3, 4).astype(mm)
    dtq = dt.reshape(Bsz, nc, Q, H).transpose(1, 0, 2, 3).astype(jnp.float32)
    Bq = Bm.reshape(Bsz, nc, Q, G, N).transpose(1, 0, 2, 3, 4).astype(mm)
    Cq = Cm.reshape(Bsz, nc, Q, G, N).transpose(1, 0, 2, 3, 4).astype(mm)

    def step(s_prev, inp):
        xc, dtc, Bc, Cc = inp                      # (B,Q,...)
        dA = dtc * A[None, None, :]                # (B,Q,H) negative, fp32
        cum = jnp.cumsum(dA, axis=1)
        total = cum[:, -1]                         # (B,H)
        # intra-chunk: M[i,j] = exp(cum_i - cum_j), i >= j; masked before
        # the exp, whose overflow above the diagonal would make the
        # gradient 0 * inf
        diff = cum[:, :, None, :] - cum[:, None, :, :]       # (B,Qi,Qj,H)
        decay = jnp.exp(jnp.where(mask[None, :, :, None], diff, -jnp.inf))
        cb = jnp.einsum("bign,bjgn->bijg", Cc, Bc, **acc32)  # (B,Q,Q,G)
        cb = jnp.repeat(cb, rep, axis=3)                     # (B,Q,Q,H)
        xdt = xc * dtc[..., None].astype(mm)                 # (B,Q,H,P)
        scores = (cb * decay).astype(mm)
        y_intra = jnp.einsum("bijh,bjhp->bihp", scores, xdt, **acc32)
        # inter-chunk: y_i += exp(cum_i) C_i . S_prev
        Ch = jnp.repeat(Cc, rep, axis=2)                     # (B,Q,H,N)
        y_inter = jnp.einsum("bqhn,bhnp->bqhp",
                             (Ch.astype(jnp.float32)
                              * jnp.exp(cum)[..., None]).astype(mm),
                             s_prev.astype(mm), **acc32)
        # state update: S = S_prev * exp(total) + sum_j exp(total-cum_j) B_j xdt_j
        sdecay = jnp.exp(total[:, None, :] - cum)            # (B,Q,H)
        Bh = jnp.repeat(Bc, rep, axis=2)                     # (B,Q,H,N)
        s_new = (s_prev * jnp.exp(total)[:, :, None, None]
                 + jnp.einsum("bqhn,bqhp->bhnp",
                              (Bh.astype(jnp.float32)
                               * sdecay[..., None]).astype(mm),
                              xdt, **acc32))
        return s_new, y_intra + y_inter

    s0 = (jnp.zeros((Bsz, H, N, P), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))
    s_final, ys = jax.lax.scan(step, s0, (xq, dtq, Bq, Cq))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(Bsz, L, H, P)
    return y.astype(xh.dtype), s_final


def apply_mamba2(params: Params, x: jnp.ndarray, cfg: SSMConfig,
                 conv_state: Optional[jnp.ndarray] = None,
                 ssm_state: Optional[jnp.ndarray] = None,
                 return_state: bool = False):
    """Full-sequence forward.  x: (B, L, D).

    Each part runs under a ``jax.named_scope`` (``mamba2_in_proj``,
    ``mamba2_conv1d``, ``mamba2_ssd``, ``mamba2_gate_norm``,
    ``mamba2_out_proj``), which the compiled program keeps in its
    operations' ``op_name``, forward, recomputed and transposed."""
    Bsz, L, _ = x.shape
    H, P, ng, ns = cfg.n_heads, cfg.head_dim, cfg.n_groups, cfg.d_state
    with jax.named_scope("mamba2_in_proj"):
        z, xin, Bc, Cc, dt = _split_proj(params, x, cfg)
    with jax.named_scope("mamba2_conv1d"):
        conv_in = jnp.concatenate([xin, Bc, Cc], axis=-1)
        conv_out = causal_conv1d_ref(conv_in, params["conv_w"],
                                     params["conv_b"], conv_state)
        xin, Bc, Cc = jnp.split(conv_out, [cfg.d_inner, cfg.d_inner + ng * ns],
                                axis=-1)
    with jax.named_scope("mamba2_ssd"):
        A = -jnp.exp(params["a_log"])                       # (H,) negative
        dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
        xh = xin.reshape(Bsz, L, H, P)
        Bm = Bc.reshape(Bsz, L, ng, ns)
        Cm = Cc.reshape(Bsz, L, ng, ns)
        y, s_final = ssd_chunked(xh, dt, A, Bm, Cm, min(cfg.chunk, L),
                                 init_state=ssm_state, mm_dtype=cfg.mm_dtype)
        y = y + params["d_skip"][None, None, :, None] * xh
        y = y.reshape(Bsz, L, cfg.d_inner).astype(x.dtype)
    with jax.named_scope("mamba2_gate_norm"):
        y = rmsnorm(y * jax.nn.silu(z), params["norm_scale"], cfg.norm_eps)
    with jax.named_scope("mamba2_out_proj"):
        out = jnp.einsum("bld,dk->blk", y, params["w_out"]).astype(x.dtype)
    if return_state:
        new_conv_state = jnp.concatenate(
            [jnp.zeros((Bsz, cfg.conv_width - 1, cfg.conv_dim), x.dtype),
             conv_in], axis=1)[:, -(cfg.conv_width - 1):]
        return out, (new_conv_state, s_final)
    return out


def decode_step(params: Params, x_t: jnp.ndarray, state, cfg: SSMConfig):
    """O(1) recurrent step.  x_t: (B, D); state = (conv_state, ssm_state).
    Its parts run under ``apply_mamba2``'s scopes."""
    conv_state, ssm_state = state
    Bsz = x_t.shape[0]
    H, P, ng, ns = cfg.n_heads, cfg.head_dim, cfg.n_groups, cfg.d_state
    with jax.named_scope("mamba2_in_proj"):
        z, xin, Bc, Cc, dt = _split_proj(params, x_t, cfg)
    with jax.named_scope("mamba2_conv1d"):
        conv_in = jnp.concatenate([xin, Bc, Cc], axis=-1)   # (B, conv_dim)
        conv_out, conv_state = conv1d_step(conv_in, conv_state,
                                           params["conv_w"], params["conv_b"])
        xin, Bc, Cc = jnp.split(conv_out, [cfg.d_inner, cfg.d_inner + ng * ns],
                                axis=-1)
    with jax.named_scope("mamba2_ssd"):
        A = -jnp.exp(params["a_log"])
        dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (B,H)
        xh = xin.reshape(Bsz, H, P).astype(jnp.float32)
        Bm = jnp.repeat(Bc.reshape(Bsz, ng, ns), H // ng, axis=1)  # (B,H,N)
        Cm = jnp.repeat(Cc.reshape(Bsz, ng, ns), H // ng, axis=1)
        da = jnp.exp(dt * A[None, :])                       # (B,H)
        ssm_state = (ssm_state * da[:, :, None, None]
                     + jnp.einsum("bhn,bhp->bhnp", Bm, xh * dt[..., None]))
        y = jnp.einsum("bhn,bhnp->bhp", Cm, ssm_state)
        y = y + params["d_skip"][None, :, None] * xh
        y = y.reshape(Bsz, cfg.d_inner).astype(x_t.dtype)
    with jax.named_scope("mamba2_gate_norm"):
        y = rmsnorm(y * jax.nn.silu(z), params["norm_scale"], cfg.norm_eps)
    with jax.named_scope("mamba2_out_proj"):
        out = jnp.einsum("bd,dk->bk", y, params["w_out"]).astype(x_t.dtype)
    return out, (conv_state, ssm_state)
