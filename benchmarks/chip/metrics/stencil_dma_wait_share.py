"""stencil_dma_wait_share: the share of the Pallas stencil kernel's time
spent waiting on its DMAs (``cp.wait()``), in %: the mean
``stencil_dma_wait`` region per sampled grid step, times the grid's
steps, over the kernel's time per sweep, from the region pass run after
the window (``harness.regions``)."""

from harness import regions


def read(ctx, facts, trace):
    got = regions.readings(ctx, facts)
    if got is None or got["shares"] is None:
        return None
    return got["shares"]["stencil_dma_wait"]
