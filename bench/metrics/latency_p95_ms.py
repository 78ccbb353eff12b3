"""latency_p95_ms: 95th percentile over every request of the window, from
when it was due to when its response was read."""
import numpy as np


def read(ctx):
    if not ctx.latencies_s:
        return None
    return float(np.percentile(ctx.latencies_s, 95)) * 1e3
