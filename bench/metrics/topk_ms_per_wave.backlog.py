"""topk_ms_per_wave.backlog: device time of the top-K programs per wave."""


def read(ctx):
    s = ctx.summary
    if s is None or s.waves == 0:
        return None
    return s.topk_s / s.waves * 1e3
