"""The device's idle share of the traced window, in %: one minus the
union of its operations' intervals over the window."""


def read(ctx, facts, trace):
    if trace is None:
        return None
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s())
