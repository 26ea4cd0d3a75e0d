"""train_mfu: model FLOPs per token (6·N plus the causal attention term,
``harness.counts``) times tokens per second of the window, over the
chip's bf16 peak, in %.  Recomputed operations do not count."""


def read(ctx, facts, trace):
    if "flops_per_token" not in facts:
        return None
    rate = facts["tokens"] / facts["window_s"]
    return 100.0 * facts["flops_per_token"] * rate / \
        ctx.peaks()["bf16_flops_s"]
