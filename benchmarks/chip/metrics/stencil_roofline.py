"""stencil_roofline: the Pallas stencil kernel's share of its roofline,
in %.  The least time of a sweep is its minimal bytes over the chip's
HBM bandwidth (its operations bound it far less); the same work is
counted whatever plan implements it.  Kernel time is the device time of
the kernel's custom calls in the traced window."""


def read(ctx, facts, trace):
    if trace is None or "min_bytes_per_sweep" not in facts:
        return None
    kernel = trace.kernel_s()
    if kernel <= 0:
        return None
    least = facts["units"] * facts["min_bytes_per_sweep"] / \
        ctx.peaks()["hbm_bytes_s"]
    return 100.0 * least / kernel
