"""Operations and bytes of a Mamba-2 language model's training, computed
from the configuration file's sizes alone (its own keys: ``d_model``,
``n_layer``, ``d_state``, ``headdim``, ...).

Kept apart from the program, as ``harness.counts`` is, so that no change
to the program moves the yardstick.
"""

from __future__ import annotations

from typing import Dict


def sizes(cfg: Dict) -> Dict[str, int]:
    d, n, g = cfg["d_model"], cfg["d_state"], cfg["ngroups"]
    di = cfg["expand"] * d
    h = di // cfg["headdim"]
    conv = di + 2 * g * n
    proj = 2 * di + 2 * g * n + h
    pad = cfg["pad_vocab_size_multiple"]
    vocab = -(-cfg["vocab_size"] // pad) * pad
    # in_proj, conv weight and bias, A_log, dt_bias, D, the gated norm's
    # scale, out_proj, the pre-norm's scale
    layer = d * proj + (cfg["d_conv"] + 1) * conv + 3 * h + di + di * d + d
    return {"d": d, "di": di, "h": h, "p": cfg["headdim"], "n": n, "g": g,
            "layers": cfg["n_layer"], "vocab": vocab, "per_layer": layer,
            # tied embedding, counted once; the final norm's scale
            "params": vocab * d + cfg["n_layer"] * layer + d}


def ssd_forward_flops(cfg: Dict, seq_len: int) -> float:
    """The matrix products of one layer's chunked SSD over one sequence:
    within each chunk of ``chunk_size`` the causal half of ``C B^T`` and
    of the scores times ``x dt``; the chunks' states read (``C`` against
    the state) and written (``B`` against ``x dt``)."""
    s = sizes(cfg)
    q = min(cfg["chunk_size"], seq_len)
    intra = 2.0 * seq_len * (q / 2) * (s["g"] * s["n"] + s["h"] * s["p"])
    states = 2 * 2.0 * seq_len * s["h"] * s["n"] * s["p"]
    return intra + states


def ssd_forward_min_bytes(cfg: Dict, seq_len: int, itemsize: int = 2) -> float:
    """One layer's SSD over one sequence reads ``x``, ``B``, ``C`` (in the
    compute dtype) and ``dt`` (float32) once and writes ``y`` once."""
    s = sizes(cfg)
    xy = 2 * seq_len * s["di"] * itemsize
    bc = 2 * seq_len * s["g"] * s["n"] * itemsize
    return xy + bc + seq_len * s["h"] * 4


def ssd_train_step(cfg: Dict, seq_len: int, batch: int) -> Dict[str, float]:
    """A train step's SSD work over every layer: the forward pass and a
    backward pass of twice its operations and bytes (it reads the
    forward's inputs and ``dy``, and writes their gradients);
    recomputation does not count."""
    n = 3.0 * batch * cfg["n_layer"]
    return {"flops": n * ssd_forward_flops(cfg, seq_len),
            "min_bytes": n * ssd_forward_min_bytes(cfg, seq_len)}


def train_flops_per_token(cfg: Dict, seq_len: int) -> float:
    """6·N per token plus the SSD's matrix products (forward and
    backward)."""
    ssd = 3.0 * cfg["n_layer"] * ssd_forward_flops(cfg, seq_len) / seq_len
    return 6.0 * sizes(cfg)["params"] + ssd

