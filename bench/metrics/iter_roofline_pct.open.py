"""iter_roofline_pct.open: the eq. (1) iteration's share of its HBM bound
in the open-loop cells (``roofline.iteration_roofline_pct``)."""
from bench.roofline import iteration_roofline_pct


def read(ctx):
    return iteration_roofline_pct(ctx)
