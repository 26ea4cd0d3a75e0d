"""fetch_bytes_ratio: the bytes the chosen fetch plan's DMAs copy in one
sweep (the program's own count, ``FetchPlan.bytes_per_block`` times the
blocks), over the sweep's minimal bytes (``harness.counts``)."""


def read(ctx, facts, trace):
    if "plan_bytes_per_sweep" not in facts:
        return None
    return facts["plan_bytes_per_sweep"] / facts["min_bytes_per_sweep"]
