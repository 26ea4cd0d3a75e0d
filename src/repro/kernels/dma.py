"""HBM -> VMEM copy geometry shared by the Pallas TPU kernels.

A TPU DMA moves whole memory tiles: along the last (lane) axis of an
array a tile is 128 elements, along the second-to-last (sublane) axis it
is 8 rows of 32-bit data (16 of 16-bit data); leading axes are untiled.
A copy whose start or size along a tiled axis is not a tile multiple is
refused by the compiler, so every halo window is widened to whole tiles
here, and the kernel serves its taps as static shifted slices of the
widened VMEM buffer.
"""

from __future__ import annotations

from typing import Tuple

LANES = 128


def sublanes(itemsize: int) -> int:
    """Rows per sublane tile for an element of ``itemsize`` bytes."""
    return 8 * 4 // itemsize


def axis_tiles(ndim: int, itemsize: int) -> Tuple[int, ...]:
    """Tile extent of each axis of an ``ndim``-axis array in HBM."""
    tiles = [1] * ndim
    tiles[-1] = LANES
    if ndim >= 2:
        tiles[-2] = sublanes(itemsize)
    return tuple(tiles)


def aligned_window(start: int, size: int, tile: int) -> Tuple[int, int]:
    """Widen ``[start, start + size)`` to whole tiles.

    Returns ``(base, length)``: the window ``[base, base + length)``
    starts at a tile boundary at or below ``start`` (``start >= 0``) and
    spans whole tiles covering the requested range.
    """
    base = start // tile * tile
    return base, -(-(start - base + size) // tile) * tile
