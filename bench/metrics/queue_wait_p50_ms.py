"""queue_wait_p50_ms: median of the service's admission-wait samples (submit
to wave launch) over the window; the telemetry is reset after set-up."""


def read(ctx):
    return ctx.sample_median_ms("ppr_admission_wait_seconds_quantiles")
