"""queue_wait_p50_ms: median of the service's admission-wait samples (submit
to wave launch) over the window; the telemetry is reset after set-up."""
import numpy as np


def read(ctx):
    waits = [v for r in ctx.family("ppr_admission_wait_seconds_quantiles")
             for v in r.values()]
    if not waits:
        return None
    return float(np.percentile(waits, 50)) * 1e3
