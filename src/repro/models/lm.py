"""Unified language-model assembly for the ten assigned architectures.

One :class:`Model` per family, all sharing the same API:

  init(key)                         -> boxed params (LogicalArray leaves)
  loss(params, batch)               -> (scalar, metrics)       [train]
  prefill(params, batch)            -> (last logits, cache)    [serve]
  decode_step(params, tokens, cache)-> (logits, cache)         [serve]
  init_cache(batch_size, seq_len)   -> cache pytree            [serve]

``build_model(cfg, mesh)`` is the factory.  All full-sequence paths scan
over layers (compact HLO for the 61-100 layer dry-runs); per-family
heterogeneity (VLM cross layers, Zamba shared block) is expressed as
scans over homogeneous *supercells*.

Cross-entropy is computed in sequence chunks (``lax.scan``) so the
(B, S, vocab) logit tensor is never materialized — at kimi-k2 train_4k
that tensor would be 687 TB.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from . import attention as attn
from . import mamba2 as m2
from . import mlp as mlpm
from . import moe as moem
from .common import (
    EMBED,
    LAYERS,
    Params,
    apply_norm,
    embed_init,
    init_norm,
    larray,
    stacked_init,
    unbox,
    VOCAB,
)

CE_CHUNK = 512


def _pad_kv(kv: jnp.ndarray, max_len: Optional[int]) -> jnp.ndarray:
    """Pad a stacked KV cache (..., S, KV, Dh) along S to ``max_len`` so
    decode steps have room to append."""
    if max_len is None or kv.shape[-3] >= max_len:
        return kv
    pad = [(0, 0)] * kv.ndim
    pad[-3] = (0, max_len - kv.shape[-3])
    return jnp.pad(kv, pad)


def _dtype(cfg: ModelConfig):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[cfg.dtype]


def _attn_cfg(cfg: ModelConfig, causal: bool = True) -> attn.AttnConfig:
    return attn.AttnConfig(
        d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta, causal=causal,
        q_block=cfg.q_block, kv_block=cfg.kv_block)


# ---------------------------------------------------------------------------
# chunked cross-entropy
# ---------------------------------------------------------------------------

def chunked_ce_loss(table: jnp.ndarray, hidden: jnp.ndarray,
                    labels: jnp.ndarray, chunk: int = CE_CHUNK,
                    valid_vocab: Optional[int] = None):
    """hidden: (B, S, D); labels: (B, S) (-1 = masked).  Mean NLL.

    ``valid_vocab``: when the embedding table is padded to a lane
    multiple (cfg.pad_vocab_multiple), rows >= valid_vocab get a -inf
    logit bias so the padding never enters the softmax."""
    B, S, D = hidden.shape
    c = min(chunk, S)
    assert S % c == 0
    nc = S // c
    hc = hidden.reshape(B, nc, c, D).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, nc, c).transpose(1, 0, 2)

    def step(carry, inp):
        tot, cnt = carry
        h, l = inp
        logits = jnp.einsum("bcd,vd->bcv", h.astype(jnp.float32),
                            table.astype(jnp.float32))
        if valid_vocab is not None and valid_vocab < table.shape[0]:
            pad_mask = jnp.arange(table.shape[0]) >= valid_vocab
            logits = jnp.where(pad_mask[None, None], -1e30, logits)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(l, 0)[..., None], axis=-1)[..., 0]
        mask = (l >= 0).astype(jnp.float32)
        tot = tot + jnp.sum((logz - gold) * mask)
        cnt = cnt + jnp.sum(mask)
        return (tot, cnt), None

    (tot, cnt), _ = jax.lax.scan(step, (jnp.float32(0), jnp.float32(0)),
                                 (hc, lc))
    return tot / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# transformer block (dense / moe ffn)
# ---------------------------------------------------------------------------

def init_tblock(key, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(key, 4)
    p: Params = {
        "ln1": init_norm(ks[0], cfg.d_model, cfg.norm, dtype),
        "attn": attn.init_attention(ks[1], _attn_cfg(cfg), dtype),
        "ln2": init_norm(ks[2], cfg.d_model, cfg.norm, dtype),
    }
    if cfg.n_experts:
        p["moe"] = moem.init_moe(ks[3], cfg.d_model, cfg.d_ff, cfg.n_experts,
                                 cfg.moe_top_k, dtype)
    else:
        p["mlp"] = mlpm.init_mlp(ks[3], cfg.d_model, cfg.d_ff, cfg.mlp, dtype)
    return p


def _apply_ffn(p: Params, x, cfg: ModelConfig, mesh):
    if cfg.n_experts:
        if cfg.moe_impl == "sharded" and mesh is not None:
            y, aux = moem.apply_moe_sharded(p["moe"], x, cfg.moe_top_k,
                                            cfg.n_experts, mesh,
                                            schedule=cfg.moe_schedule)
        else:
            y, aux = moem.apply_moe_dense(p["moe"], x, cfg.moe_top_k,
                                          cfg.n_experts)
        return y, aux
    return mlpm.apply_mlp(p["mlp"], x, cfg.mlp), jnp.float32(0)


def apply_tblock(p: Params, x, cfg: ModelConfig, mesh):
    from repro.sharding.rules import constrain_batch
    x = constrain_batch(x, mesh)
    h = apply_norm(p["ln1"], x, cfg.norm, impl=cfg.norm_impl)
    x = x + attn.self_attention(p["attn"], h, _attn_cfg(cfg),
                                impl=cfg.attn_impl, mesh=mesh)
    x = constrain_batch(x, mesh)
    h = apply_norm(p["ln2"], x, cfg.norm, impl=cfg.norm_impl)
    y, aux = _apply_ffn(p, h, cfg, mesh)
    return constrain_batch(x + y, mesh), aux


def prefill_tblock(p: Params, x, cfg: ModelConfig, mesh):
    from repro.sharding.rules import constrain_batch
    x = constrain_batch(x, mesh)
    h = apply_norm(p["ln1"], x, cfg.norm, impl=cfg.norm_impl)
    a, kv = attn.prefill_attention(p["attn"], h, _attn_cfg(cfg),
                                   impl=cfg.attn_impl, mesh=mesh)
    x = constrain_batch(x + a, mesh)
    h = apply_norm(p["ln2"], x, cfg.norm, impl=cfg.norm_impl)
    y, _ = _apply_ffn(p, h, cfg, mesh)
    return constrain_batch(x + y, mesh), kv


def decode_tblock(p: Params, x, kv_cache, pos, cfg: ModelConfig, mesh):
    h = apply_norm(p["ln1"], x, cfg.norm, impl=cfg.norm_impl)
    a, kv_cache = attn.decode_attention(p["attn"], h, kv_cache, pos,
                                        _attn_cfg(cfg))
    x = x + a
    h = apply_norm(p["ln2"], x, cfg.norm, impl=cfg.norm_impl)
    y, _ = _apply_ffn(p, h, cfg, mesh)
    return x + y, kv_cache


# ---------------------------------------------------------------------------
# base model
# ---------------------------------------------------------------------------

class Model:
    """Base: embedding + scanned homogeneous transformer stack."""

    def __init__(self, cfg: ModelConfig, mesh=None):
        self.cfg = cfg
        self.mesh = mesh
        self.dtype = _dtype(cfg)

    # -- params -----------------------------------------------------------
    def init(self, key) -> Params:
        cfg = self.cfg
        k_emb, k_blocks, k_ln = jax.random.split(key, 3)
        params: Params = {
            "embed": {"table": larray(
                embed_init(k_emb, (cfg.padded_vocab, cfg.d_model), self.dtype),
                VOCAB, EMBED)},
            "blocks": stacked_init(
                lambda k: init_tblock(k, cfg, self.dtype), k_blocks,
                cfg.n_layers),
            "ln_f": init_norm(k_ln, cfg.d_model, cfg.norm, self.dtype),
        }
        return params

    # -- full-sequence forward ---------------------------------------------
    def _backbone(self, params: Params, x: jnp.ndarray,
                  batch: Dict[str, jnp.ndarray]):
        cfg, mesh = self.cfg, self.mesh

        def block(x, bp):
            y, aux = apply_tblock(bp, x, cfg, mesh)
            return y, aux

        if cfg.remat == "block":
            block = jax.checkpoint(block)
        x, auxs = jax.lax.scan(lambda c, p: block(c, p), x, params["blocks"])
        return x, jnp.sum(auxs)

    def hidden(self, params: Params, batch: Dict[str, jnp.ndarray]):
        x = params["embed"]["table"][batch["tokens"]]
        x, aux = self._backbone(params, x, batch)
        return apply_norm(params["ln_f"], x, self.cfg.norm, impl=self.cfg.norm_impl), aux

    def loss(self, params: Params, batch: Dict[str, jnp.ndarray]):
        h, aux = self.hidden(params, batch)
        ce = chunked_ce_loss(params["embed"]["table"], h, batch["labels"],
                             valid_vocab=self.cfg.vocab)
        metrics = {"ce": ce, "aux": aux}
        return ce + 0.01 * aux, metrics

    # -- serving ------------------------------------------------------------
    def init_cache(self, batch_size: int, seq_len: int) -> Dict[str, Any]:
        cfg = self.cfg
        L, KV, Dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
        z = jnp.zeros((L, batch_size, seq_len, KV, Dh), self.dtype)
        return {"k": z, "v": z,
                "pos": jnp.zeros((batch_size,), jnp.int32)}

    def prefill(self, params: Params, batch: Dict[str, jnp.ndarray],
                max_len: Optional[int] = None):
        cfg, mesh = self.cfg, self.mesh
        x = params["embed"]["table"][batch["tokens"]]

        def block(x, bp):
            y, kv = prefill_tblock(bp, x, cfg, mesh)
            return y, kv

        x, (ks, vs) = jax.lax.scan(block, x, params["blocks"])
        h = apply_norm(params["ln_f"], x, cfg.norm, impl=cfg.norm_impl)
        logits = jnp.einsum("bd,vd->bv", h[:, -1].astype(jnp.float32),
                            params["embed"]["table"].astype(jnp.float32))
        logits = logits[:, :cfg.vocab]
        B, S = batch["tokens"].shape
        cache = {"k": _pad_kv(ks, max_len), "v": _pad_kv(vs, max_len),
                 "pos": jnp.full((B,), S, jnp.int32)}
        return logits, cache

    def decode_step(self, params: Params, tokens: jnp.ndarray, cache):
        """tokens: (B,) int32 -> (logits (B, V), new cache)."""
        cfg, mesh = self.cfg, self.mesh
        x = params["embed"]["table"][tokens][:, None]     # (B,1,D)
        pos = cache["pos"]

        def block(x, inp):
            bp, ck, cv = inp
            y, (ck, cv) = decode_tblock(bp, x, (ck, cv), pos, cfg, mesh)
            return y, (ck, cv)

        x, (ks, vs) = jax.lax.scan(block, x,
                                   (params["blocks"], cache["k"], cache["v"]))
        h = apply_norm(params["ln_f"], x[:, 0], cfg.norm, impl=cfg.norm_impl)
        logits = jnp.einsum("bd,vd->bv", h.astype(jnp.float32),
                            params["embed"]["table"].astype(jnp.float32))
        logits = logits[:, :cfg.vocab]
        return logits, {"k": ks, "v": vs, "pos": pos + 1}


# ---------------------------------------------------------------------------
# VLM: self layers + periodic cross-attention layers (supercell scan)
# ---------------------------------------------------------------------------

class VLMModel(Model):
    """cross_every-1 self blocks + 1 cross block per supercell."""

    def __init__(self, cfg: ModelConfig, mesh=None):
        assert cfg.cross_every > 1 and cfg.n_layers % cfg.cross_every == 0
        super().__init__(cfg, mesh)
        self.n_super = cfg.n_layers // cfg.cross_every
        self.n_self = cfg.cross_every - 1

    def init(self, key) -> Params:
        cfg = self.cfg
        k_emb, k_self, k_cross, k_ln = jax.random.split(key, 4)

        def init_super_self(k):
            return stacked_init(lambda kk: init_tblock(kk, cfg, self.dtype),
                                k, self.n_self)

        def init_cross(k):
            ks = jax.random.split(k, 4)
            return {
                "ln1": init_norm(ks[0], cfg.d_model, cfg.norm, self.dtype),
                "xattn": attn.init_attention(ks[1], _attn_cfg(cfg, False),
                                             self.dtype),
                "ln2": init_norm(ks[2], cfg.d_model, cfg.norm, self.dtype),
                "mlp": mlpm.init_mlp(ks[3], cfg.d_model, cfg.d_ff, cfg.mlp,
                                     self.dtype),
                "gate": larray(jnp.zeros((), self.dtype)),
            }

        return {
            "embed": {"table": larray(
                embed_init(k_emb, (cfg.padded_vocab, cfg.d_model), self.dtype),
                VOCAB, EMBED)},
            "super_self": stacked_init(init_super_self, k_self, self.n_super),
            "super_cross": stacked_init(init_cross, k_cross, self.n_super),
            "ln_f": init_norm(k_ln, cfg.d_model, cfg.norm, self.dtype),
        }

    def _apply_cross(self, cp, x, media):
        cfg = self.cfg
        h = apply_norm(cp["ln1"], x, cfg.norm, impl=cfg.norm_impl)
        # tanh-gated cross attention (Llama-3.2-Vision style)
        x = x + jnp.tanh(cp["gate"]) * attn.cross_attention(
            cp["xattn"], h, media, _attn_cfg(cfg, causal=False))
        h = apply_norm(cp["ln2"], x, cfg.norm, impl=cfg.norm_impl)
        return x + mlpm.apply_mlp(cp["mlp"], h, cfg.mlp)

    def _backbone(self, params, x, batch):
        cfg, mesh = self.cfg, self.mesh
        media = batch["media"].astype(self.dtype)

        def supercell(x, sp):
            selfp, crossp = sp

            def sblock(x, bp):
                y, aux = apply_tblock(bp, x, cfg, mesh)
                return y, aux

            if cfg.remat == "block":
                sblock = jax.checkpoint(sblock)
            x, auxs = jax.lax.scan(sblock, x, selfp)
            x = self._apply_cross(crossp, x, media)
            return x, jnp.sum(auxs)

        x, auxs = jax.lax.scan(supercell, x,
                               (params["super_self"], params["super_cross"]))
        return x, jnp.sum(auxs)

    def hidden(self, params, batch):
        x = params["embed"]["table"][batch["tokens"]]
        x, aux = self._backbone(params, x, batch)
        return apply_norm(params["ln_f"], x, self.cfg.norm, impl=self.cfg.norm_impl), aux

    # serving: cache self-attn KV per (supercell, layer); media memory fixed
    def init_cache(self, batch_size: int, seq_len: int):
        cfg = self.cfg
        KV, Dh = cfg.n_kv_heads, cfg.head_dim
        z = jnp.zeros((self.n_super, self.n_self, batch_size, seq_len, KV, Dh),
                      self.dtype)
        media = jnp.zeros((batch_size, cfg.n_media_tokens, cfg.d_model),
                          self.dtype)
        return {"k": z, "v": z, "media": media,
                "pos": jnp.zeros((batch_size,), jnp.int32)}

    def prefill(self, params, batch, max_len: Optional[int] = None):
        cfg, mesh = self.cfg, self.mesh
        media = batch["media"].astype(self.dtype)
        x = params["embed"]["table"][batch["tokens"]]

        def supercell(x, sp):
            selfp, crossp = sp

            def sblock(x, bp):
                y, kv = prefill_tblock(bp, x, cfg, mesh)
                return y, kv

            x, kvs = jax.lax.scan(sblock, x, selfp)
            x = self._apply_cross(crossp, x, media)
            return x, kvs

        x, (ks, vs) = jax.lax.scan(supercell, x,
                                   (params["super_self"],
                                    params["super_cross"]))
        h = apply_norm(params["ln_f"], x, cfg.norm, impl=cfg.norm_impl)
        logits = jnp.einsum("bd,vd->bv", h[:, -1].astype(jnp.float32),
                            params["embed"]["table"].astype(jnp.float32))
        logits = logits[:, :cfg.vocab]
        B, S = batch["tokens"].shape
        return logits, {"k": _pad_kv(ks, max_len), "v": _pad_kv(vs, max_len),
                        "media": media,
                        "pos": jnp.full((B,), S, jnp.int32)}

    def decode_step(self, params, tokens, cache):
        cfg, mesh = self.cfg, self.mesh
        x = params["embed"]["table"][tokens][:, None]
        pos, media = cache["pos"], cache["media"]

        def supercell(x, inp):
            (selfp, crossp), ck, cv = inp

            def sblock(x, i2):
                bp, k1, v1 = i2
                y, (k1, v1) = decode_tblock(bp, x, (k1, v1), pos, cfg, mesh)
                return y, (k1, v1)

            x, (ck, cv) = jax.lax.scan(sblock, x, (selfp, ck, cv))
            x = self._apply_cross(crossp, x, media)
            return x, (ck, cv)

        x, (ks, vs) = jax.lax.scan(
            supercell, x,
            ((params["super_self"], params["super_cross"]),
             cache["k"], cache["v"]))
        h = apply_norm(params["ln_f"], x[:, 0], cfg.norm, impl=cfg.norm_impl)
        logits = jnp.einsum("bd,vd->bv", h.astype(jnp.float32),
                            params["embed"]["table"].astype(jnp.float32))
        logits = logits[:, :cfg.vocab]
        return logits, {"k": ks, "v": vs, "media": media, "pos": pos + 1}


# ---------------------------------------------------------------------------
# SSM (mamba2) model
# ---------------------------------------------------------------------------

class SSMModel(Model):
    """Pre-norm Mamba-2 blocks over a float32 residual stream (the
    published ``residual_in_fp32``): each norm reads the float32 residual
    and hands the mixer and the loss the compute dtype."""

    def _ssm_cfg(self) -> m2.SSMConfig:
        cfg = self.cfg
        return m2.SSMConfig(d_model=cfg.d_model, d_state=cfg.ssm_state,
                            head_dim=cfg.ssm_head_dim, expand=cfg.ssm_expand,
                            conv_width=cfg.conv_width, chunk=cfg.ssm_chunk,
                            norm_eps=cfg.ssm_norm_eps,
                            mm_dtype=cfg.ssm_mm_dtype)

    def _norm(self, p, x):
        cfg = self.cfg
        return apply_norm(p, x, cfg.norm, impl=cfg.norm_impl,
                          eps=cfg.ssm_norm_eps).astype(self.dtype)

    def _embed(self, params, tokens):
        return params["embed"]["table"][tokens].astype(jnp.float32)

    def init(self, key) -> Params:
        cfg = self.cfg
        scfg = self._ssm_cfg()
        k_emb, k_blocks, k_ln = jax.random.split(key, 3)

        def init_block(k):
            k1, k2 = jax.random.split(k)
            return {"ln": init_norm(k1, cfg.d_model, cfg.norm, self.dtype),
                    "mamba": m2.init_mamba2(k2, scfg, self.dtype)}

        return {
            "embed": {"table": larray(
                embed_init(k_emb, (cfg.padded_vocab, cfg.d_model), self.dtype),
                VOCAB, EMBED)},
            "blocks": stacked_init(init_block, k_blocks, cfg.n_layers),
            "ln_f": init_norm(k_ln, cfg.d_model, cfg.norm, self.dtype),
        }

    def _backbone(self, params, x, batch):
        cfg = self.cfg
        scfg = self._ssm_cfg()
        from repro.sharding.rules import constrain_batch

        def block(x, bp):
            x = constrain_batch(x, self.mesh)
            y = m2.apply_mamba2(bp["mamba"], self._norm(bp["ln"], x), scfg)
            return constrain_batch(x + y.astype(x.dtype), self.mesh), \
                jnp.float32(0)

        if cfg.remat == "block":
            block = jax.checkpoint(block)
        x, _ = jax.lax.scan(block, x, params["blocks"])
        return x, jnp.float32(0)

    def hidden(self, params, batch):
        x, aux = self._backbone(params, self._embed(params, batch["tokens"]),
                                batch)
        return self._norm(params["ln_f"], x), aux

    def init_cache(self, batch_size: int, seq_len: int):
        scfg = self._ssm_cfg()
        L = self.cfg.n_layers
        conv = jnp.zeros((L, batch_size, scfg.conv_width - 1, scfg.conv_dim),
                         self.dtype)
        ssm = jnp.zeros((L, batch_size, scfg.n_heads, scfg.d_state,
                         scfg.head_dim), jnp.float32)
        return {"conv": conv, "ssm": ssm,
                "pos": jnp.zeros((batch_size,), jnp.int32)}

    def prefill(self, params, batch, max_len: Optional[int] = None):
        cfg = self.cfg
        scfg = self._ssm_cfg()
        x = self._embed(params, batch["tokens"])

        from repro.sharding.rules import constrain_batch

        def block(x, bp):
            x = constrain_batch(x, self.mesh)
            y, (cs, ss) = m2.apply_mamba2(bp["mamba"], self._norm(bp["ln"], x),
                                          scfg, return_state=True)
            return constrain_batch(x + y.astype(x.dtype), self.mesh), (cs, ss)

        x, (convs, ssms) = jax.lax.scan(block, x, params["blocks"])
        h = self._norm(params["ln_f"], x)
        logits = jnp.einsum("bd,vd->bv", h[:, -1].astype(jnp.float32),
                            params["embed"]["table"].astype(jnp.float32))
        logits = logits[:, :cfg.vocab]
        B, S = batch["tokens"].shape
        return logits, {"conv": convs.astype(self.dtype), "ssm": ssms,
                        "pos": jnp.full((B,), S, jnp.int32)}

    def decode_step(self, params, tokens, cache):
        cfg = self.cfg
        scfg = self._ssm_cfg()
        x = self._embed(params, tokens)                  # (B, D)

        def block(x, inp):
            bp, cs, ss = inp
            y, (cs, ss) = m2.decode_step(bp["mamba"], self._norm(bp["ln"], x),
                                         (cs, ss), scfg)
            return x + y.astype(x.dtype), (cs, ss)

        x, (convs, ssms) = jax.lax.scan(
            block, x, (params["blocks"], cache["conv"], cache["ssm"]))
        h = self._norm(params["ln_f"], x)
        logits = jnp.einsum("bd,vd->bv", h.astype(jnp.float32),
                            params["embed"]["table"].astype(jnp.float32))
        logits = logits[:, :cfg.vocab]
        return logits, {"conv": convs, "ssm": ssms, "pos": cache["pos"] + 1}


# ---------------------------------------------------------------------------
# hybrid (zamba2): mamba backbone + one shared attention block
# ---------------------------------------------------------------------------

class HybridModel(SSMModel):
    """Supercells of (shared attn block + attn_every mamba blocks) plus
    trailing mamba blocks; the attention block weights are SHARED across
    all applications (Zamba's parameter-sharing trick)."""

    def __init__(self, cfg: ModelConfig, mesh=None):
        super().__init__(cfg, mesh)
        self.n_super = cfg.n_layers // cfg.attn_every
        self.n_trail = cfg.n_layers - self.n_super * cfg.attn_every

    def _embed(self, params, tokens):
        # the hybrid's residual stays in the compute dtype
        return params["embed"]["table"][tokens]

    def init(self, key) -> Params:
        cfg = self.cfg
        scfg = self._ssm_cfg()
        ks = jax.random.split(key, 5)

        def init_mblock(k):
            k1, k2 = jax.random.split(k)
            return {"ln": init_norm(k1, cfg.d_model, cfg.norm, self.dtype),
                    "mamba": m2.init_mamba2(k2, scfg, self.dtype)}

        def init_super(k):
            return stacked_init(init_mblock, k, cfg.attn_every)

        params = {
            "embed": {"table": larray(
                embed_init(ks[0], (cfg.padded_vocab, cfg.d_model), self.dtype),
                VOCAB, EMBED)},
            "supers": stacked_init(init_super, ks[1], self.n_super),
            "shared_attn": init_tblock(ks[2], cfg, self.dtype),
            "ln_f": init_norm(ks[3], cfg.d_model, cfg.norm, self.dtype),
        }
        if self.n_trail:
            params["trail"] = stacked_init(init_mblock, ks[4], self.n_trail)
        return params

    def _backbone(self, params, x, batch):
        cfg, mesh = self.cfg, self.mesh
        scfg = self._ssm_cfg()

        def mblock(x, bp):
            h = self._norm(bp["ln"], x)
            return x + m2.apply_mamba2(bp["mamba"], h, scfg), None

        from repro.sharding.rules import constrain_batch

        def mblock_c(x, bp):
            x = constrain_batch(x, mesh)
            y, _ = mblock(x, bp)
            return constrain_batch(y, mesh), None

        if cfg.remat == "block":
            mblock_c = jax.checkpoint(mblock_c)

        def supercell(x, sp):
            x = apply_tblock(params["shared_attn"], x, cfg, mesh)[0]
            x, _ = jax.lax.scan(mblock_c, x, sp)
            return x, None

        if cfg.remat == "block":
            supercell = jax.checkpoint(supercell)

        x, _ = jax.lax.scan(supercell, x, params["supers"])
        if self.n_trail:
            x, _ = jax.lax.scan(mblock_c, x, params["trail"])
        return x, jnp.float32(0)

    def init_cache(self, batch_size: int, seq_len: int):
        cfg = self.cfg
        scfg = self._ssm_cfg()
        L = cfg.n_layers
        conv = jnp.zeros((L, batch_size, scfg.conv_width - 1, scfg.conv_dim),
                         self.dtype)
        ssm = jnp.zeros((L, batch_size, scfg.n_heads, scfg.d_state,
                         scfg.head_dim), jnp.float32)
        KV, Dh = cfg.n_kv_heads, cfg.head_dim
        kv = jnp.zeros((self.n_super, batch_size, seq_len, KV, Dh),
                       self.dtype)
        return {"conv": conv, "ssm": ssm, "attn_k": kv, "attn_v": kv,
                "pos": jnp.zeros((batch_size,), jnp.int32)}

    def prefill(self, params, batch, max_len: Optional[int] = None):
        cfg, mesh = self.cfg, self.mesh
        scfg = self._ssm_cfg()
        x = self._embed(params, batch["tokens"])

        from repro.sharding.rules import constrain_batch

        def mblock(x, bp):
            x = constrain_batch(x, mesh)
            h = self._norm(bp["ln"], x)
            y, (cs, ss) = m2.apply_mamba2(bp["mamba"], h, scfg,
                                          return_state=True)
            return constrain_batch(x + y, mesh), (cs, ss)

        def supercell(x, sp):
            x, kv = prefill_tblock(params["shared_attn"], x, cfg, mesh)
            x, states = jax.lax.scan(mblock, x, sp)
            return x, (states, kv)

        x, ((convs, ssms), (ks, vs)) = jax.lax.scan(supercell, x,
                                                    params["supers"])
        conv_all = convs.reshape((-1,) + convs.shape[2:])
        ssm_all = ssms.reshape((-1,) + ssms.shape[2:])
        if self.n_trail:
            x, (ct, st) = jax.lax.scan(mblock, x, params["trail"])
            conv_all = jnp.concatenate([conv_all, ct], 0)
            ssm_all = jnp.concatenate([ssm_all, st], 0)
        h = self._norm(params["ln_f"], x)
        logits = jnp.einsum("bd,vd->bv", h[:, -1].astype(jnp.float32),
                            params["embed"]["table"].astype(jnp.float32))
        logits = logits[:, :cfg.vocab]
        B, S = batch["tokens"].shape
        return logits, {"conv": conv_all.astype(self.dtype), "ssm": ssm_all,
                        "attn_k": _pad_kv(ks, max_len),
                        "attn_v": _pad_kv(vs, max_len),
                        "pos": jnp.full((B,), S, jnp.int32)}

    def decode_step(self, params, tokens, cache):
        cfg, mesh = self.cfg, self.mesh
        scfg = self._ssm_cfg()
        x = self._embed(params, tokens)
        pos = cache["pos"]
        ne = cfg.attn_every

        def mblock(x, inp):
            bp, cs, ss = inp
            h = self._norm(bp["ln"], x)
            y, (cs, ss) = m2.decode_step(bp["mamba"], h, (cs, ss), scfg)
            return x + y, (cs, ss)

        n_in_super = self.n_super * ne
        conv_s = cache["conv"][:n_in_super].reshape(
            (self.n_super, ne) + cache["conv"].shape[1:])
        ssm_s = cache["ssm"][:n_in_super].reshape(
            (self.n_super, ne) + cache["ssm"].shape[1:])

        def supercell(x, inp):
            sp, cs, ss, ck, cv = inp
            x2d = x[:, None]
            y, (ck, cv) = decode_tblock(params["shared_attn"], x2d,
                                        (ck, cv), pos, cfg, mesh)
            x = y[:, 0]
            x, (cs, ss) = jax.lax.scan(mblock, x, (sp, cs, ss))
            return x, (cs, ss, ck, cv)

        x, (convs, ssms, ks, vs) = jax.lax.scan(
            supercell, x, (params["supers"], conv_s, ssm_s,
                           cache["attn_k"], cache["attn_v"]))
        conv_all = convs.reshape((-1,) + convs.shape[2:])
        ssm_all = ssms.reshape((-1,) + ssms.shape[2:])
        if self.n_trail:
            x, (ct, st) = jax.lax.scan(
                mblock, x, (params["trail"], cache["conv"][n_in_super:],
                            cache["ssm"][n_in_super:]))
            conv_all = jnp.concatenate([conv_all, ct], 0)
            ssm_all = jnp.concatenate([ssm_all, st], 0)
        h = self._norm(params["ln_f"], x)
        logits = jnp.einsum("bd,vd->bv", h.astype(jnp.float32),
                            params["embed"]["table"].astype(jnp.float32))
        logits = logits[:, :cfg.vocab]
        return logits, {"conv": conv_all, "ssm": ssm_all,
                        "attn_k": ks, "attn_v": vs, "pos": pos + 1}


# ---------------------------------------------------------------------------
# encoder-decoder (seamless): stubbed frame embeddings -> text decoder
# ---------------------------------------------------------------------------

class EncDecModel(Model):
    def init(self, key) -> Params:
        cfg = self.cfg
        ks = jax.random.split(key, 6)

        def init_enc_block(k):
            k1, k2, k3, k4 = jax.random.split(k, 4)
            return {
                "ln1": init_norm(k1, cfg.d_model, cfg.norm, self.dtype),
                "attn": attn.init_attention(k2, _attn_cfg(cfg, False),
                                            self.dtype),
                "ln2": init_norm(k3, cfg.d_model, cfg.norm, self.dtype),
                "mlp": mlpm.init_mlp(k4, cfg.d_model, cfg.d_ff, cfg.mlp,
                                     self.dtype),
            }

        def init_dec_block(k):
            k1, k2, k3, k4, k5, k6 = jax.random.split(k, 6)
            return {
                "ln1": init_norm(k1, cfg.d_model, cfg.norm, self.dtype),
                "attn": attn.init_attention(k2, _attn_cfg(cfg), self.dtype),
                "lnx": init_norm(k3, cfg.d_model, cfg.norm, self.dtype),
                "xattn": attn.init_attention(k4, _attn_cfg(cfg, False),
                                             self.dtype),
                "ln2": init_norm(k5, cfg.d_model, cfg.norm, self.dtype),
                "mlp": mlpm.init_mlp(k6, cfg.d_model, cfg.d_ff, cfg.mlp,
                                     self.dtype),
            }

        return {
            "embed": {"table": larray(
                embed_init(ks[0], (cfg.padded_vocab, cfg.d_model), self.dtype),
                VOCAB, EMBED)},
            "enc_blocks": stacked_init(init_enc_block, ks[1],
                                       cfg.n_encoder_layers),
            "enc_ln": init_norm(ks[2], cfg.d_model, cfg.norm, self.dtype),
            "dec_blocks": stacked_init(init_dec_block, ks[3], cfg.n_layers),
            "ln_f": init_norm(ks[4], cfg.d_model, cfg.norm, self.dtype),
        }

    def encode(self, params, frames: jnp.ndarray) -> jnp.ndarray:
        """frames: (B, F, D) stubbed speech embeddings -> memory."""
        cfg = self.cfg
        x = frames.astype(self.dtype)
        acfg = _attn_cfg(cfg, causal=False)

        from repro.sharding.rules import constrain_batch

        def block(x, bp):
            x = constrain_batch(x, self.mesh)
            h = apply_norm(bp["ln1"], x, cfg.norm, impl=cfg.norm_impl)
            x = x + attn.self_attention(bp["attn"], h, acfg,
                                        impl=cfg.attn_impl, mesh=self.mesh)
            h = apply_norm(bp["ln2"], x, cfg.norm, impl=cfg.norm_impl)
            return constrain_batch(x + mlpm.apply_mlp(bp["mlp"], h, cfg.mlp),
                                   self.mesh), None

        if cfg.remat == "block":
            block = jax.checkpoint(block)
        x, _ = jax.lax.scan(block, x, params["enc_blocks"])
        return apply_norm(params["enc_ln"], x, cfg.norm, impl=cfg.norm_impl)

    def _dec_block(self, bp, x, memory, mesh):
        cfg = self.cfg
        from repro.sharding.rules import constrain_batch
        x = constrain_batch(x, mesh)
        h = apply_norm(bp["ln1"], x, cfg.norm, impl=cfg.norm_impl)
        x = x + attn.self_attention(bp["attn"], h, _attn_cfg(cfg),
                                    impl=cfg.attn_impl, mesh=mesh)
        h = apply_norm(bp["lnx"], x, cfg.norm, impl=cfg.norm_impl)
        x = x + attn.cross_attention(bp["xattn"], h, memory,
                                     _attn_cfg(cfg, False))
        h = apply_norm(bp["ln2"], x, cfg.norm, impl=cfg.norm_impl)
        return x + mlpm.apply_mlp(bp["mlp"], h, cfg.mlp)

    def hidden(self, params, batch):
        cfg = self.cfg
        memory = self.encode(params, batch["frames"])
        x = params["embed"]["table"][batch["tokens"]]

        def block(x, bp):
            return self._dec_block(bp, x, memory, self.mesh), None

        if cfg.remat == "block":
            block = jax.checkpoint(block)
        x, _ = jax.lax.scan(block, x, params["dec_blocks"])
        return apply_norm(params["ln_f"], x, cfg.norm, impl=cfg.norm_impl), jnp.float32(0)

    def init_cache(self, batch_size: int, seq_len: int):
        cfg = self.cfg
        KV, Dh = cfg.n_kv_heads, cfg.head_dim
        z = jnp.zeros((cfg.n_layers, batch_size, seq_len, KV, Dh), self.dtype)
        mem = jnp.zeros((batch_size, cfg.n_frames, cfg.d_model), self.dtype)
        return {"k": z, "v": z, "memory": mem,
                "pos": jnp.zeros((batch_size,), jnp.int32)}

    def prefill(self, params, batch, max_len: Optional[int] = None):
        cfg = self.cfg
        memory = self.encode(params, batch["frames"])
        x = params["embed"]["table"][batch["tokens"]]

        from repro.sharding.rules import constrain_batch

        def block(x, bp):
            x = constrain_batch(x, self.mesh)
            h = apply_norm(bp["ln1"], x, cfg.norm, impl=cfg.norm_impl)
            a, kv = attn.prefill_attention(bp["attn"], h, _attn_cfg(cfg),
                                           impl=cfg.attn_impl, mesh=self.mesh)
            x = x + a
            h = apply_norm(bp["lnx"], x, cfg.norm, impl=cfg.norm_impl)
            x = x + attn.cross_attention(bp["xattn"], h, memory,
                                         _attn_cfg(cfg, False))
            h = apply_norm(bp["ln2"], x, cfg.norm, impl=cfg.norm_impl)
            return x + mlpm.apply_mlp(bp["mlp"], h, cfg.mlp), kv

        x, (ks, vs) = jax.lax.scan(block, x, params["dec_blocks"])
        h = apply_norm(params["ln_f"], x, cfg.norm, impl=cfg.norm_impl)
        logits = jnp.einsum("bd,vd->bv", h[:, -1].astype(jnp.float32),
                            params["embed"]["table"].astype(jnp.float32))
        logits = logits[:, :cfg.vocab]
        B, S = batch["tokens"].shape
        return logits, {"k": _pad_kv(ks, max_len), "v": _pad_kv(vs, max_len),
                        "memory": memory,
                        "pos": jnp.full((B,), S, jnp.int32)}

    def decode_step(self, params, tokens, cache):
        cfg = self.cfg
        x = params["embed"]["table"][tokens][:, None]
        pos, memory = cache["pos"], cache["memory"]

        def block(x, inp):
            bp, ck, cv = inp
            h = apply_norm(bp["ln1"], x, cfg.norm, impl=cfg.norm_impl)
            a, (ck, cv) = attn.decode_attention(bp["attn"], h, (ck, cv), pos,
                                                _attn_cfg(cfg))
            x = x + a
            h = apply_norm(bp["lnx"], x, cfg.norm, impl=cfg.norm_impl)
            x = x + attn.cross_attention(bp["xattn"], h, memory,
                                         _attn_cfg(cfg, False))
            h = apply_norm(bp["ln2"], x, cfg.norm, impl=cfg.norm_impl)
            return x + mlpm.apply_mlp(bp["mlp"], h, cfg.mlp), (ck, cv)

        x, (ks, vs) = jax.lax.scan(block, x,
                                   (params["dec_blocks"], cache["k"],
                                    cache["v"]))
        h = apply_norm(params["ln_f"], x[:, 0], cfg.norm, impl=cfg.norm_impl)
        logits = jnp.einsum("bd,vd->bv", h.astype(jnp.float32),
                            params["embed"]["table"].astype(jnp.float32))
        logits = logits[:, :cfg.vocab]
        return logits, {"k": ks, "v": vs, "memory": memory, "pos": pos + 1}


# ---------------------------------------------------------------------------
# factory
# ---------------------------------------------------------------------------

def build_model(cfg: ModelConfig, mesh=None) -> Model:
    return {
        "dense": Model,
        "moe": Model,
        "vlm": VLMModel,
        "ssm": SSMModel,
        "hybrid": HybridModel,
        "audio": EncDecModel,
    }[cfg.family](cfg, mesh)
