"""Plain reference of OLMo-1B (arXiv:2402.00838; the HF ``olmo`` model).

Straightforward jax.numpy in float32 at ``Precision.HIGHEST``, with no
kernels, no cache and no batching tricks: token embedding; per layer a
non-parametric LayerNorm (no scale, no bias, eps 1e-5), multi-head
causal self-attention with rotary embeddings (rotate-half convention,
theta from the config) and no biases, a residual add, a second
non-parametric LayerNorm, a SwiGLU MLP (``silu(x W_gate) * (x W_up)``
then ``W_down``) and a residual add; a final LayerNorm; logits against
the tied embedding table.  The loss is the mean next-token
cross-entropy.  The optimizer is AdamW with global-norm clipping,
linear warm-up then cosine decay, and decoupled weight decay on every
weight matrix, as the configuration's optimizer entry states.

Weights are made here from the seed (``init_weights``), in the dtype the
configuration stores them in; the program is given the same arrays.
Layers are stacked on a leading axis.

``quant="fp8"`` is the control: every matrix product takes its two
operands through float8 (e4m3) with a per-tensor scale, the precision
below the configuration's bfloat16.
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
LN_EPS = 1e-5
LEAVES = ("embed", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def sizes(cfg: Dict) -> Dict[str, int]:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"D": d, "H": h, "KV": cfg["num_key_value_heads"], "Dh": d // h,
            "F": cfg["intermediate_size"], "L": cfg["num_hidden_layers"],
            "V": cfg["vocab_size"]}


def leaf_shapes(cfg: Dict) -> Dict[str, tuple]:
    s = sizes(cfg)
    D, H, KV, Dh, F, L, V = (s[k] for k in ("D", "H", "KV", "Dh", "F", "L", "V"))
    return {"embed": (V, D), "wq": (L, D, H, Dh), "wk": (L, D, KV, Dh),
            "wv": (L, D, KV, Dh), "wo": (L, H, Dh, D), "w_gate": (L, D, F),
            "w_up": (L, D, F), "w_down": (L, F, D)}


def leaf_std(name: str, cfg: Dict) -> float:
    s = sizes(cfg)
    fan_in = {"embed": None, "wq": s["D"], "wk": s["D"], "wv": s["D"],
              "wo": s["H"] * s["Dh"], "w_gate": s["D"], "w_up": s["D"],
              "w_down": s["F"]}[name]
    return 0.02 if fan_in is None else fan_in ** -0.5


def dtype_of(cfg: Dict):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[cfg["torch_dtype"]]


def init_leaf(key, name: str, cfg: Dict):
    """One weight array from the run's key (leaf ``i`` of ``LEAVES`` uses
    ``fold_in(key, i)``), normal with the leaf's std, in the stored dtype."""
    k = jax.random.fold_in(key, LEAVES.index(name))
    w = jax.random.normal(k, leaf_shapes(cfg)[name], jnp.float32)
    return (w * leaf_std(name, cfg)).astype(dtype_of(cfg))


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


def layer_norm(x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS)


def rope(x, theta: float):
    """x: (B, S, H, Dh); positions 0..S-1; rotate-half convention."""
    S, Dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, Dh, 2, dtype=np.float64) / Dh))
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1), jnp.float32)
    sin = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1), jnp.float32)
    half = Dh // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[None, :, None, :] + rot * sin[None, :, None, :]


def block(x, lw, cfg: Dict, quant=None):
    s = sizes(cfg)
    theta = float(cfg["rope_theta"])
    h = layer_norm(x)
    q = rope(_mm("bsd,dhk->bshk", h, lw["wq"], quant), theta)
    k = rope(_mm("bsd,dhk->bshk", h, lw["wk"], quant), theta)
    v = _mm("bsd,dhk->bshk", h, lw["wv"], quant)
    g = s["H"] // s["KV"]
    if g > 1:
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    scores = _mm("bqhk,bshk->bhqs", q, k, quant) / math.sqrt(s["Dh"])
    S = x.shape[1]
    causal = np.tril(np.ones((S, S), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = _mm("bhqs,bshk->bqhk", p, v, quant)
    x = x + _mm("bshk,hkd->bsd", o, lw["wo"], quant)
    h = layer_norm(x)
    gate = _mm("bsd,df->bsf", h, lw["w_gate"], quant)
    up = _mm("bsd,df->bsf", h, lw["w_up"], quant)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, lw["w_down"], quant)


def hidden(w, tokens, cfg: Dict, quant=None):
    """Final-LayerNorm hidden states (B, S, D), float32."""
    x = w["embed"].astype(jnp.float32)[tokens]
    layers = {n: w[n] for n in LEAVES if n != "embed"}

    def body(x, lw):
        lw = {n: a.astype(jnp.float32) for n, a in lw.items()}
        return block(x, lw, cfg, quant), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, layers)
    return layer_norm(x)


def logits(w, h, quant=None):
    return _mm("bsd,vd->bsv", h, w["embed"].astype(jnp.float32), quant)


def loss(w, tokens, labels, cfg: Dict, quant=None, keep=None):
    """Mean cross-entropy over the first ``keep`` positions (all when
    ``keep`` is None)."""
    lg = logits(w, hidden(w, tokens, cfg, quant), quant)[:, :keep]
    labels = labels[:, :keep]
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


# ---------------------------------------------------------------------------
# serving: logits at chosen positions of one sequence
# ---------------------------------------------------------------------------

def position_logits(w, tokens, first: int, cfg: Dict, quant=None):
    """Logits (n, V) at positions ``first..S-1`` of ``tokens`` (1, S)."""
    h = hidden(w, tokens, cfg, quant)[:, first:]
    return logits(w, h, quant)[0]


def served_gap(w, tokens, first: int, served, cfg: Dict, quant=None):
    """For the tokens ``served`` after positions ``first..``: the widest
    gap by which a served token's reference logit lies below the
    reference's best, or (with ``quant``) the same for the tokens the
    quantized reference itself would put first."""
    ref = position_logits(w, tokens, first, cfg)
    best = jnp.max(ref, axis=-1)
    if quant is None:
        picked = served
    else:
        picked = jnp.argmax(position_logits(w, tokens, first, cfg, quant), -1)
    return jnp.max(best - jnp.take_along_axis(ref, picked[:, None], -1)[:, 0])


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


def _adamw(p, g, m, v, scale, lr, b1c, b2c, opt):
    g = g * scale
    m = opt["b1"] * m + (1 - opt["b1"]) * g
    v = opt["b2"] * v + (1 - opt["b2"]) * jnp.square(g)
    upd = (m / b1c) / (jnp.sqrt(v / b2c) + opt["eps"])
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

    adamw = jax.jit(functools.partial(_adamw, opt=opt))
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
            p_host[n], m[n], v[n] = adamw(p_host[n], g[n], m[n], v[n], scale,
                                          lr, b1c, b2c)
        del g
        w = {n: jax.device_put(a, jax.devices()[0]) for n, a in p_host.items()}
        jax.block_until_ready(w)
        seconds["host"] += time.perf_counter() - t1
    change = {n: math.sqrt(float(sq(p_host[n].astype(jnp.float32)
                                    - p0[n].astype(jnp.float32))))
              for n in LEAVES}
    return {"losses": losses, "grad_norms": first_grad, "change_norms": change,
            "seconds": seconds, **out}
