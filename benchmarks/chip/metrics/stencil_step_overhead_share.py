"""stencil_step_overhead_share: the share of the Pallas stencil kernel's
time outside its three step regions (DMA issue, DMA wait, compute), in
%: 100 less the three regions' shares from the region pass run after
the window (``harness.regions``); the grid loop, the output block's
writeback and whatever else runs between step bodies."""

from harness import regions


def read(ctx, facts, trace):
    got = regions.readings(ctx, facts)
    if got is None or got["shares"] is None:
        return None
    return got["shares"][regions.OVERHEAD]
