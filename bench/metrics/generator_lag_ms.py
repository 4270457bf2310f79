"""99th percentile of how late the load generator sent a request: its
send time minus its due time, over the window's requests."""

import numpy as np

from bench.harness.stats import percentile


def read(ctx):
    if ctx.lag_s is None:
        return None
    lag = ctx.lag_s[np.isfinite(ctx.lag_s)]
    return percentile(lag * 1e3, 99) if len(lag) else None
