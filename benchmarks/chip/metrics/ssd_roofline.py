"""ssd_roofline: the SSD's share of its roofline, in %.  The least time
of a train step's SSD is the larger of its operations over the chip's
bf16 peak and its minimal bytes over the HBM bandwidth
(``harness.ssm_counts``: the chunked form's causal halves, forward and
backward, recomputation not counted); times the window's steps, over the
device time of the operations under the scope ``mamba2_ssd``."""

from harness import scopes


def read(ctx, facts, trace):
    if "ssd_train_step" not in facts:
        return None
    s = scopes.read_scope_s(ctx, facts, "mamba2_ssd")
    if not s:
        return None
    work, peaks = facts["ssd_train_step"], ctx.peaks()
    least = max(work["flops"] / peaks["bf16_flops_s"],
                work["min_bytes"] / peaks["hbm_bytes_s"])
    return 100.0 * facts["units"] * least / s
