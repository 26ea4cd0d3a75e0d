"""Plain reference of Mamba-2 1.3B (arXiv:2405.21060; state-spaces/mamba2-1.3b).

Straightforward jax.numpy in float32 at ``Precision.HIGHEST``, with no
kernels, no chunking, no cache and no batching tricks: token embedding;
per layer a pre-norm RMSNorm of the float32 residual, the Mamba-2 mixer
and a residual add; a final RMSNorm; logits against the tied embedding
table; the mean next-token cross-entropy over every row of the table.
The mixer, per the published ``Mamba2`` module:

- ``in_proj`` (no bias) to ``[z, x, B, C, dt]``;
- the depthwise causal conv1d (width ``d_conv``, with bias) over
  ``[x, B, C]`` as ``d_conv`` shifted products, then SiLU;
- ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` per head;
- the SSD in its quadratic (dual) form, independent of any chunking:
  ``y = (L o C B^T) (x dt) + D x`` with ``L_ij = exp(cum_i - cum_j)``
  for ``i >= j`` (else 0), ``cum`` the running sum of ``dt A``; each
  layer's is computed in blocks of ``HEAD_BLOCK`` heads so that the
  (heads, S, S) mask fits;
- the gated RMSNorm ``norm(y * silu(z))`` over ``d_inner`` and
  ``out_proj`` (no bias).

Every RMSNorm takes the file's ``norm_epsilon``.  The optimizer is AdamW
with global-norm clipping, linear warm-up then cosine decay, and
decoupled weight decay on the published set (``DECAYED``): the
embedding and each layer's matrices (``in_proj``, the conv1d's weight,
``out_proj``), and none on ``A_log``, ``D``, ``dt_bias``, the norms'
scales or the conv1d's bias.

Departures from the published model: weights are seeded, not trained
(``init_weights``, the file's ``assumed`` init); only ``ngroups`` 1 is
computed; the ``dt_limit`` clamp is left out (its default (0, inf)
clamps nothing).

Leaves are named by their whole path in the program's parameter tree
(``blocks/ln/scale``, ``ln_f/scale``), layers stacked on a leading axis.
``quant="fp8"`` is the control: every matrix product, the SSD's two
included, takes its operands through float8 (e4m3) with a per-tensor
scale, the precision below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
import math
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
HEAD_BLOCK = 16
LEAVES = ("embed/table", "blocks/ln/scale", "blocks/mamba/w_in",
          "blocks/mamba/conv_w", "blocks/mamba/conv_b", "blocks/mamba/a_log",
          "blocks/mamba/dt_bias", "blocks/mamba/d_skip",
          "blocks/mamba/norm_scale", "blocks/mamba/w_out", "ln_f/scale")
# weight decay applies to these alone: the published model marks A_log,
# D and dt_bias as no-weight-decay, and norms and biases take none
DECAYED = ("embed/table", "blocks/mamba/w_in", "blocks/mamba/conv_w",
           "blocks/mamba/w_out")
# kept in float32 whatever the stored dtype, as the published model does
FLOAT32_LEAVES = ("blocks/mamba/a_log", "blocks/mamba/dt_bias",
                  "blocks/mamba/d_skip")


def sizes(cfg: Dict) -> Dict[str, int]:
    if cfg["ngroups"] != 1:
        raise ValueError(f"ngroups {cfg['ngroups']}: the reference computes 1")
    d, n = cfg["d_model"], cfg["d_state"]
    di = cfg["expand"] * d
    h = di // cfg["headdim"]
    pad = cfg["pad_vocab_size_multiple"]
    return {"D": d, "DI": di, "H": h, "P": cfg["headdim"], "N": n,
            "W": cfg["d_conv"], "C": di + 2 * n, "PROJ": 2 * di + 2 * n + h,
            "L": cfg["n_layer"], "V": -(-cfg["vocab_size"] // pad) * pad}


def leaf_shapes(cfg: Dict) -> Dict[str, tuple]:
    s = sizes(cfg)
    L, D, DI, H = s["L"], s["D"], s["DI"], s["H"]
    return {"embed/table": (s["V"], D), "blocks/ln/scale": (L, D),
            "blocks/mamba/w_in": (L, D, s["PROJ"]),
            "blocks/mamba/conv_w": (L, s["W"], s["C"]),
            "blocks/mamba/conv_b": (L, s["C"]),
            "blocks/mamba/a_log": (L, H), "blocks/mamba/dt_bias": (L, H),
            "blocks/mamba/d_skip": (L, H),
            "blocks/mamba/norm_scale": (L, DI),
            "blocks/mamba/w_out": (L, DI, D), "ln_f/scale": (D,)}


def dtype_of(name: str, cfg: Dict):
    if name in FLOAT32_LEAVES:
        return jnp.float32
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[cfg["torch_dtype"]]


def _draw(k, name: str, cfg: Dict):
    """The file's ``assumed`` init, in float32."""
    s = sizes(cfg)
    shape = leaf_shapes(cfg)[name]
    uniform = lambda bound: jax.random.uniform(k, shape, jnp.float32,
                                               -bound, bound)
    if name == "embed/table":
        return 0.02 * jax.random.normal(k, shape, jnp.float32)
    if name == "blocks/mamba/w_in":
        return uniform(s["D"] ** -0.5)
    if name == "blocks/mamba/w_out":
        return uniform(s["DI"] ** -0.5) / math.sqrt(s["L"])
    if name in ("blocks/mamba/conv_w", "blocks/mamba/conv_b"):
        return uniform(s["W"] ** -0.5)
    if name == "blocks/mamba/a_log":
        lo, hi = cfg["A_init_range"]
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, lo, hi))
    if name == "blocks/mamba/dt_bias":
        lo, hi = math.log(cfg["dt_min"]), math.log(cfg["dt_max"])
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, lo, hi))
        dt = jnp.maximum(dt, cfg["dt_init_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))         # inverse softplus
    return jnp.ones(shape, jnp.float32)              # norms' scales, D


def init_leaf(key, name: str, cfg: Dict):
    """One weight array from the run's key (leaf ``i`` of ``LEAVES`` uses
    ``fold_in(key, i)``), in its stored dtype."""
    k = jax.random.fold_in(key, LEAVES.index(name))
    return _draw(k, name, cfg).astype(dtype_of(name, cfg))


def init_weights(key, cfg: Dict) -> Dict[str, jnp.ndarray]:
    return {n: init_leaf(key, n, cfg) for n in LEAVES}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

FP8_MAX = 448.0       # largest finite float8_e4m3fn


def fake_quant(x, quant: Optional[str]):
    """``x`` rounded to float8 (e4m3) under a per-tensor scale that maps
    its largest magnitude to the format's largest, and back; gradients
    pass straight through the rounding."""
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(quant)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(eq, a, b, quant):
    return jnp.einsum(eq, fake_quant(a, quant), fake_quant(b, quant),
                      precision=HIGHEST)


def rms_norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def causal_conv(x, w, b):
    """x: (B, S, C); w: (W, C); tap ``k`` reads position ``i - (W-1) + k``
    (zero before the start)."""
    W, S = w.shape[0], x.shape[1]
    out = b
    for k in range(W):
        shift = W - 1 - k
        xs = jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, :S]
        out = out + xs * w[k]
    return jax.nn.silu(out)


def ssd(xh, dt, A, Bm, Cm, quant=None):
    """The quadratic form.  xh: (B, S, H, P); dt: (B, S, H); A: (H,);
    Bm, Cm: (B, S, N).  Returns (B, S, H, P), without the D term."""
    Bsz, S, H, P = xh.shape
    hb = min(HEAD_BLOCK, H)
    if H % hb:
        raise ValueError(f"{H} heads in blocks of {hb}")
    nb = H // hb
    cb = _mm("bin,bjn->bij", Cm, Bm, quant)                 # (B, S, S)
    causal = np.tril(np.ones((S, S), bool))[None, :, :, None]
    cum = jnp.cumsum(dt * A, axis=1)                         # (B, S, H)
    xdt = xh * dt[..., None]

    def block(args):
        cum_b, xdt_b = args                                  # hb heads
        seg = cum_b[:, :, None, :] - cum_b[:, None, :, :]    # (B, i, j, hb)
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        return _mm("bijh,bjhp->bihp", decay * cb[..., None], xdt_b, quant)

    heads_first = lambda a: jnp.moveaxis(
        a.reshape(a.shape[:2] + (nb, hb) + a.shape[3:]), 2, 0)
    ys = jax.lax.map(jax.checkpoint(block), (heads_first(cum),
                                             heads_first(xdt)))
    return jnp.moveaxis(ys, 0, 2).reshape(Bsz, S, H, P)


def mixer(h, lw, cfg: Dict, quant=None):
    s = sizes(cfg)
    DI, N, H, P = s["DI"], s["N"], s["H"], s["P"]
    Bsz, S, _ = h.shape
    proj = _mm("bsd,dk->bsk", h, lw["w_in"], quant)
    z, xbc, dt = jnp.split(proj, [DI, 2 * DI + 2 * N], axis=-1)
    xbc = causal_conv(xbc, lw["conv_w"], lw["conv_b"])
    x, Bm, Cm = jnp.split(xbc, [DI, DI + N], axis=-1)
    dt = jax.nn.softplus(dt + lw["dt_bias"])
    A = -jnp.exp(lw["a_log"])
    xh = x.reshape(Bsz, S, H, P)
    y = ssd(xh, dt, A, Bm, Cm, quant) + lw["d_skip"][:, None] * xh
    y = rms_norm(y.reshape(Bsz, S, DI) * jax.nn.silu(z), lw["norm_scale"],
                 cfg["norm_epsilon"])
    return _mm("bse,ed->bsd", y, lw["w_out"], quant)


def hidden(w, tokens, cfg: Dict, quant=None):
    """Final-norm hidden states (B, S, D), float32; the residual stream is
    float32 throughout."""
    eps = cfg["norm_epsilon"]
    x = w["embed/table"].astype(jnp.float32)[tokens]
    layers = {n.split("/")[-1]: w[n] for n in LEAVES if n.startswith("blocks/")}

    def body(x, lw):
        lw = {n: a.astype(jnp.float32) for n, a in lw.items()}
        return x + mixer(rms_norm(x, lw["scale"], eps), lw, cfg, quant), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, layers)
    return rms_norm(x, w["ln_f/scale"].astype(jnp.float32), eps)


def loss(w, tokens, labels, cfg: Dict, quant=None, keep=None):
    """Mean cross-entropy over the first ``keep`` positions (all when
    ``keep`` is None), against every row of the tied table."""
    h = hidden(w, tokens, cfg, quant)
    lg = _mm("bsd,vd->bsv", h, w["embed/table"].astype(jnp.float32),
             quant)[:, :keep]
    labels = labels[:, :keep]
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


# ---------------------------------------------------------------------------
# training: three AdamW steps
# ---------------------------------------------------------------------------

def lr_at(opt: Dict, step: int) -> float:
    """The learning rate of optimizer step ``step`` (1-based)."""
    if step < opt["warmup_steps"]:
        return opt["lr"] * step / max(opt["warmup_steps"], 1)
    prog = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    return opt["lr"] * (opt["min_lr_frac"] + (1 - opt["min_lr_frac"])
                        * 0.5 * (1 + math.cos(math.pi * prog)))


def _adamw(p, g, m, v, scale, lr, b1c, b2c, opt, decay):
    g = g * scale
    m = opt["b1"] * m + (1 - opt["b1"]) * g
    v = opt["b2"] * v + (1 - opt["b2"]) * jnp.square(g)
    upd = (m / b1c) / (jnp.sqrt(v / b2c) + opt["eps"])
    if decay:
        upd = upd + opt["weight_decay"] * p.astype(jnp.float32)
    return (p.astype(jnp.float32) - lr * upd).astype(p.dtype), m, v


def train_steps(key, cfg: Dict, opt: Dict, batches, quant=None,
                half_batch=False, grad_against=None, keep_grad=False):
    """Three (or ``len(batches)``) AdamW steps from the seeded weights.

    Returns the loss of each step, the norm of each leaf of the first
    step's gradient as the optimizer takes it (after clipping), and the
    norm of each leaf's change over all the steps.  Gradients are taken
    on the default device; the optimizer's moments live on the host's
    CPU device, since weights, gradients and both moments in float32 do
    not fit one chip together.  ``half_batch`` leaves the second half of
    each batch's tokens out of the loss (a fault the check must catch).

    ``seconds`` gives the time of the gradients (on the default device,
    with their compile) and of the rest (the host's share).
    ``grad_against`` (leaf name to a float32 array on the host) adds
    ``grad_diff_norms``: per leaf, the norm of this first gradient less
    that one.  ``keep_grad`` adds ``first_grad``, this first gradient's
    arrays on the host's CPU device.
    """
    cpu = jax.devices("cpu")[0]
    w = jax.jit(lambda k: init_weights(k, cfg))(key)

    keep = batches[0][0].shape[1] // 2 if half_batch else None

    @jax.jit
    def grad_fn(w, tokens, labels):
        w32 = {n: a.astype(jnp.float32) for n, a in w.items()}
        return jax.value_and_grad(loss)(w32, tokens, labels, cfg, quant, keep)

    adamw = {d: jax.jit(functools.partial(_adamw, opt=opt, decay=d))
             for d in (False, True)}
    sq = jax.jit(lambda x: jnp.sum(jnp.square(x.astype(jnp.float32))))
    diff_sq = jax.jit(lambda a, b: jnp.sum(jnp.square(a - b)))
    scaled = jax.jit(lambda a, s: a * s)
    p_host = {n: jax.device_put(a, cpu) for n, a in w.items()}
    p0 = dict(p_host)
    # both moments start at zero, held as scalars until the first step
    # writes them: the host then holds no weight-sized zeros
    m = dict.fromkeys(LEAVES, 0.0)
    v = dict(m)
    losses, first_grad, out = [], {}, {}
    seconds = {"gradients": 0.0, "host": 0.0}
    for t, (tokens, labels) in enumerate(batches, start=1):
        t0 = time.perf_counter()
        lval, g = grad_fn(w, jnp.asarray(tokens), jnp.asarray(labels))
        losses.append(float(lval))
        t1 = time.perf_counter()
        seconds["gradients"] += t1 - t0
        del w
        g = {n: jax.device_put(a, cpu) for n, a in g.items()}
        gnorm = math.sqrt(sum(float(sq(a)) for a in g.values()))
        scale = min(1.0, opt["clip_norm"] / max(gnorm, 1e-12))
        if t == 1:
            first_grad = {n: math.sqrt(float(sq(a))) * scale
                          for n, a in g.items()}
            if grad_against is not None:
                out["grad_diff_norms"] = {
                    n: math.sqrt(float(diff_sq(
                        scaled(a, scale), jax.device_put(grad_against[n], cpu))))
                    for n, a in g.items()}
            if keep_grad:
                out["first_grad"] = {n: scaled(a, scale) for n, a in g.items()}
        lr = lr_at(opt, t)
        b1c, b2c = 1 - opt["b1"] ** t, 1 - opt["b2"] ** t
        for n in LEAVES:
            p_host[n], m[n], v[n] = adamw[n in DECAYED](
                p_host[n], g[n], m[n], v[n], scale, lr, b1c, b2c)
        del g
        w = {n: jax.device_put(a, jax.devices()[0]) for n, a in p_host.items()}
        jax.block_until_ready(w)
        seconds["host"] += time.perf_counter() - t1
    change = {n: math.sqrt(float(sq(p_host[n].astype(jnp.float32)
                                    - p0[n].astype(jnp.float32))))
              for n in LEAVES}
    return {"losses": losses, "grad_norms": first_grad, "change_norms": change,
            "seconds": seconds, **out}
