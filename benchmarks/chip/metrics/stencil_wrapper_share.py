"""stencil_wrapper_share: the share of the sweeps' device time spent
outside the Pallas stencil kernel (the wrapper's pad and slice), in %."""


def read(ctx, facts, trace):
    if trace is None or "min_bytes_per_sweep" not in facts:
        return None
    total = trace.op_s()
    kernel = trace.kernel_s()
    if total <= 0 or kernel <= 0:
        return None
    return 100.0 * (total - kernel) / total
