"""conv1d_share: the share of the device's busy time spent in operations
traced under the program's scope ``mamba2_conv1d`` (the depthwise causal
conv1d and its SiLU, forward, recomputed and backward), in %
(``harness.scopes``)."""

from harness import scopes


def read(ctx, facts, trace):
    s = scopes.read_scope_s(ctx, facts, "mamba2_conv1d")
    if s is None:
        return None
    return 100.0 * s / trace.busy_s()
