"""Pallas TPU kernel: depthwise causal conv1d with shuffle-synthesized reuse.

The Mamba-2 conv is a width-W (W=4) stencil along the sequence: tap t of
output position l reads x[l-W+1+t].  Run through PTXASW (see
tests/test_kernels.py::test_ptxasw_finds_conv_deltas) the symbolic
emulator proves taps are lane-shifts of one load with deltas
{1, .., W-1} — so the TPU kernel stages ONE (Bs+W-1, Bc) tile per block
in VMEM and serves all W taps as static shifted slices (the register
shuffle), instead of W separate HBM fetches (the naive plan).

Grid: (batch, seq-blocks, channel-blocks).  The halo (W-1 rows) plays
the role of the paper's corner-case handling: resolved statically by
fetch geometry, no predication (DESIGN.md §2).

``mode="naive"`` keeps one fetch per tap to expose the traffic delta in
benchmarks (paper's Original ablation).  Every fetch is an explicit
HBM -> VMEM DMA whose window is widened to whole sublane tiles
(:mod:`repro.kernels.dma`); the taps are static slices of it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..dma import aligned_window, sublanes

MODES = ("naive", "shuffle")


def _windows(mode: str, W: int, Bs: int, itemsize: int):
    """Sequence-axis DMA windows of one block, ``(base, rows)`` relative
    to the block's first row of the causally padded input, each widened
    to whole sublane tiles.  Also returns the tap -> (window, row)
    map: ``shuffle`` copies one (Bs + W - 1)-row halo window and serves
    every tap from it; ``naive`` copies one window per tap."""
    sub = sublanes(itemsize)
    if mode == "shuffle":
        wins = [aligned_window(0, Bs + W - 1, sub)]
        taps = [(0, t) for t in range(W)]
    else:
        wins = [aligned_window(t, Bs, sub) for t in range(W)]
        taps = [(t, t - wins[t][0]) for t in range(W)]
    return wins, taps


def _kernel(x_hbm, w_ref, b_ref, o_ref, *scratch, W: int, Bs: int, Bc: int,
            wins, taps, activation: bool):
    bufs, sem = scratch[:-1], scratch[-1]
    bi = pl.program_id(0)
    si = pl.program_id(1)
    ci = pl.program_id(2)
    # sequence offset into the (W-1)-left-padded input
    s0 = si * Bs
    if Bs % 8 == 0:
        s0 = pl.multiple_of(s0, 8)
    c0 = ci * Bc
    copies = []
    for n, (base, rows) in enumerate(wins):
        cp = pltpu.make_async_copy(
            x_hbm.at[bi, pl.ds(s0 + base, rows), pl.ds(c0, Bc)],
            bufs[n], sem.at[n])
        cp.start()
        copies.append(cp)
    for cp in copies:
        cp.wait()
    w = w_ref[...].astype(jnp.float32)                   # (W, Bc)
    acc = jnp.broadcast_to(b_ref[...].astype(jnp.float32), (Bs, Bc))
    for t, (n, row) in enumerate(taps):
        # tap t = static shifted slice of a staged window
        acc = acc + bufs[n][row:row + Bs].astype(jnp.float32) * w[t:t + 1]
    if activation:
        acc = jax.nn.silu(acc)
    o_ref[...] = acc.reshape(1, Bs, Bc).astype(o_ref.dtype)


def causal_conv1d(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                  mode: str = "shuffle", activation: bool = True,
                  block_seq: int = 256, block_ch: int = 128,
                  interpret: bool = False) -> jnp.ndarray:
    """x: (B, L, C); w: (W, C); b: (C,).  Returns (B, L, C)."""
    assert mode in MODES
    B, L, C = x.shape
    W = w.shape[0]
    Bs = min(block_seq, L)
    Bc = min(block_ch, C)
    Lp = -(-L // Bs) * Bs
    Cp = -(-C // Bc) * Bc
    wins, taps = _windows(mode, W, Bs, x.dtype.itemsize)
    # left halo = causal zero pad; right pad = grid alignment plus the
    # rows the last block's tile-widened windows read past its halo
    reach = max(base + rows for base, rows in wins)
    right = Lp - L + max(0, reach - Bs - (W - 1))
    xp = jnp.pad(x, ((0, 0), (W - 1, right), (0, Cp - C)))
    wp = jnp.pad(w, ((0, 0), (0, Cp - C)))
    bp = jnp.pad(b, ((0, Cp - C))).reshape(1, Cp)
    grid = (B, Lp // Bs, Cp // Bc)
    kernel = functools.partial(_kernel, W=W, Bs=Bs, Bc=Bc, wins=wins,
                               taps=taps, activation=activation)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec((W, Bc), lambda b_, s, c: (0, c)),
                  pl.BlockSpec((1, Bc), lambda b_, s, c: (0, c))],
        out_specs=pl.BlockSpec((1, Bs, Bc), lambda b_, s, c: (b_, s, c)),
        out_shape=jax.ShapeDtypeStruct((B, Lp, Cp), x.dtype),
        scratch_shapes=[pltpu.VMEM((rows, Bc), x.dtype) for _, rows in wins]
        + [pltpu.SemaphoreType.DMA((len(wins),))],
        interpret=interpret,
    )(xp, wp, bp)
    return out[:, :L, :C]


def hbm_bytes(L: int, C: int, W: int, mode: str,
              block_seq: int = 256, block_ch: int = 128,
              itemsize: int = 2) -> int:
    """Analytic HBM read traffic for the x operand: the bytes the DMAs
    copy, tile-widened windows included."""
    nb_s = -(-L // block_seq)
    nb_c = -(-C // block_ch)
    wins, _ = _windows(mode, W, block_seq, itemsize)
    per_block = sum(rows for _, rows in wins) * block_ch
    return per_block * nb_s * nb_c * itemsize
