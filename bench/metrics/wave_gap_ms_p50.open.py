"""wave_gap_ms_p50.open: median host stretch between waves in the
open-loop cells (previous wave's top-K end to this wave's first iteration,
for waves whose oldest query waited across the previous one): the program's
``ppr_wave_gap_seconds_quantiles``; the telemetry is reset after set-up."""


def read(ctx):
    return ctx.sample_median_ms("ppr_wave_gap_seconds_quantiles")
