"""decode_mfu: the decode steps' share of the chip's peak, in %.  Each
decode step's least time is the larger of its FLOPs over the bf16 peak
and its minimal bytes (weights plus the valid K and V, read once) over
the HBM bandwidth (``harness.counts``); their sum over the window's
decode steps is divided by the window, whose prefills count as time."""

from harness import counts


def read(ctx, facts, trace):
    if "decode_ctx_lens" not in facts:
        return None
    peaks = ctx.peaks()
    least = sum(counts.decode_step_min_s(ctx.config, facts["decode_batch"],
                                         n, peaks)
                for n in facts["decode_ctx_lens"])
    return 100.0 * least / facts["window_s"]
