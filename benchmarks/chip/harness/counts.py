"""Operations and bytes the work needs, computed from shapes.

These are the benchmark's own counts, kept apart from the program so
that no change to the program moves the yardstick.
"""

from __future__ import annotations

import math
from typing import Dict, Sequence


# ---------------------------------------------------------------------------
# stencils
# ---------------------------------------------------------------------------

def stencil_interior(shape: Sequence[int], halo: int) -> tuple:
    return tuple(n - 2 * halo for n in shape)


def stencil_points(shape: Sequence[int], halo: int) -> int:
    return math.prod(stencil_interior(shape, halo))


def stencil_min_bytes(shape: Sequence[int], halo: int, n_inputs: int,
                      itemsize: int = 4) -> int:
    """Every input read once, the interior output written once."""
    return itemsize * (n_inputs * math.prod(shape)
                       + stencil_points(shape, halo))


# ---------------------------------------------------------------------------
# dense decoder LM (OLMo family): copied from the program's MODEL_FLOPS
# arithmetic (6·N·D plus the attention term), from config sizes alone
# ---------------------------------------------------------------------------

def lm_sizes(cfg: Dict) -> Dict[str, int]:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    dh = d // h
    f = cfg["intermediate_size"]
    layer = d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f
    embed = cfg["vocab_size"] * d
    return {"d": d, "h": h, "kv": kv, "dh": dh, "f": f,
            "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"],
            "per_layer": layer, "embed": embed,
            # tied embedding: the gather is free, the unembedding counts once
            "params": embed + cfg["num_hidden_layers"] * layer}


def train_flops_per_token(cfg: Dict, seq_len: int) -> float:
    """6·N per token plus the causal attention term (score and PV,
    averaged over the causal triangle: context S/2)."""
    s = lm_sizes(cfg)
    attn = 6.0 / 2.0 * 4.0 * (seq_len / 2) * s["d"] * s["layers"]
    return 6.0 * s["params"] + attn


def decode_token_flops(cfg: Dict, ctx: int) -> float:
    """One generated token attending over ``ctx`` cached positions."""
    s = lm_sizes(cfg)
    return 2.0 * s["params"] + 4.0 * ctx * s["d"] * s["layers"]


def decode_step_min_bytes(cfg: Dict, batch: int, ctx: int,
                          weight_bytes: int = 2, cache_bytes: int = 2) -> float:
    """Weights read once plus the valid K and V of every row."""
    s = lm_sizes(cfg)
    kv = 2 * batch * ctx * s["layers"] * s["kv"] * s["dh"] * cache_bytes
    return s["params"] * weight_bytes + kv


def decode_step_min_s(cfg: Dict, batch: int, ctx: int,
                      peaks: Dict[str, float]) -> float:
    """The least time a decode step can take: the larger of its FLOPs
    over the FLOP peak and its minimal bytes over the HBM peak."""
    flops = batch * decode_token_flops(cfg, ctx)
    return max(flops / peaks["bf16_flops_s"],
               decode_step_min_bytes(cfg, batch, ctx) / peaks["hbm_bytes_s"])
