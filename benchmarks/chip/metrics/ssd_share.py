"""ssd_share: the share of the device's busy time spent in operations
traced under the program's scope ``mamba2_ssd`` (the chunked SSD scan
with its dt, A and D terms, forward, recomputed and backward), in %
(``harness.scopes``)."""

from harness import scopes


def read(ctx, facts, trace):
    s = scopes.read_scope_s(ctx, facts, "mamba2_ssd")
    if s is None:
        return None
    return 100.0 * s / trace.busy_s()
