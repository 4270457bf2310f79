"""Padding rows in the batches the batcher dispatched, as a share of all
dispatched rows (``ServingMetrics`` counters over the window)."""


def read(ctx):
    c = ctx.counters
    if not c or not c.get("dispatched_samples"):
        return None
    return 100.0 * c["padded_samples"] / c["dispatched_samples"]
