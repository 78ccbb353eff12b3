"""http_self_ms_p50: median of the HTTP layer's own time per answered
``POST /v1/ppr`` over the window (request read to ``submit`` returned, plus
future resolved to response drained): the program's
``ppr_http_self_seconds_quantiles``; the telemetry is reset after set-up."""


def read(ctx):
    return ctx.sample_median_ms("ppr_http_self_seconds_quantiles")
