"""The least work of one eq. (1) iteration, from the graph's shapes alone.

Whatever implements the iteration, it has to read the edge stream once
(x, y and val at 4 bytes each) and the rank matrix P_t [V, kappa] once,
and write P_{t+1} once (4 bytes an entry in float32 and in the raw uint32
fixed-point formats alike).  Padding, intermediates such as the [E, kappa]
edge products, and re-reads are the implementation's and do not count.
"""
from __future__ import annotations


def iteration_bytes(num_vertices: int, num_edges: int, kappa: int) -> int:
    return 12 * int(num_edges) + 2 * 4 * int(num_vertices) * int(kappa)


def roofline_pct(least_bytes: float, seconds: float,
                 bytes_per_s: float) -> float:
    """Share of the bandwidth bound reached: least time over time taken."""
    return 100.0 * least_bytes / bytes_per_s / seconds


def iteration_roofline_pct(ctx):
    """The eq. (1) iteration's roofline share in a traced window: least
    bytes of one iteration over the peak HBM bandwidth of the devices its
    edges are split over (``ctx.edge_shards``), divided by the device time
    per iteration, which is the device time of every program the waves ran
    except top-K over (waves x iterations), both averaged over the devices
    traced.  None where the trace saw no whole wave."""
    s = ctx.summary
    if s is None or s.waves == 0 or s.iteration_s <= 0 or ctx.peaks is None:
        return None
    per_iteration = s.iteration_s / (s.waves * ctx.iterations)
    least = iteration_bytes(ctx.num_vertices, ctx.num_edges, ctx.kappa)
    return roofline_pct(least, per_iteration,
                        ctx.peaks["hbm_bytes_per_s"] * ctx.edge_shards)
