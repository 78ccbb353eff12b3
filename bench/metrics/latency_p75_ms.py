"""latency_p75_ms: 75th percentile over every request of the window, from
when it was due to when its response was read.  Where the result cache
answers over half the requests, the median is a hit's few milliseconds and
this is the middle of the misses, the requests that wait for a wave."""
import numpy as np


def read(ctx):
    if not ctx.latencies_s:
        return None
    return float(np.percentile(ctx.latencies_s, 75)) * 1e3
