"""The one traffic generator: a traffic file's parameters + a seed -> schedule.

A traffic file (``traffic/<name>.json``) holds only parameters:

    loop          "closed": ``clients`` keep-alive clients, each sending its
                  next request when the last is answered, drawing from at
                  most ``max_requests`` vertices;
                  "open": ``rate_qps`` requests a second on a fixed schedule,
                  over ``clients`` keep-alive connections opened in set-up
                  (one more is opened whenever all are busy).
    arrivals      (open) "poisson": exponential gaps, ``rate_qps x
                  seconds`` of them, drawn from ``schedule_seed``.  Every
                  run seed gets this same schedule: at 0.8 of the sustained
                  rate the tail follows the bursts of the schedule, and
                  seeds that reordered the gaps read p95 from 680 to 1000 ms
                  (pl2e5 on one TPU v5e), while one schedule repeats.  The
                  run seed picks the graph and the query vertices.
    vertices      "uniform": seed vertices uniform without replacement, so
                  the result cache never hits; "zipf": ranks drawn from a
                  Zipf law of exponent ``zipf_s`` (the law's quantiles at
                  (i + 0.5) / N, in a seeded order, so every seed asks for
                  the same multiset of ranks), mapped to vertices through a
                  seeded popularity permutation.  Either way seed vertices
                  are drawn among those with at least one edge, as Graph500
                  draws its search keys: an isolated vertex's answer is a
                  tie of every other vertex.
    precision     the wire ``precision`` of every request; ``k`` its size.
    prefill       (zipf) "lru_steady": set-up answers what an LRU result
                  cache of the configuration's ``cache_capacity`` holds after
                  serving this law for long: the last ``cache_capacity``
                  distinct vertices of a stream drawn from it (from
                  ``schedule_seed``, so every seed holds the same ranks),
                  least recent first, so the window starts in the state a
                  deployment that runs on keeps.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

# independent random streams drawn from one seed (the graph generators
# take the run seed itself)
ORDER_STREAM, GAPS_STREAM, CHECK_STREAM, PREFILL_STREAM = 1, 2, 3, 4


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


@dataclasses.dataclass
class Schedule:
    """What the load generator sends and what set-up must answer first."""
    loop: str
    precision: object
    k: int
    vertices: np.ndarray          # request i asks for vertices[i]
    due_s: Optional[np.ndarray]   # open loop: send time of request i
    clients: int                  # connections opened in set-up
    warm: np.ndarray              # answered in set-up (compiles the shapes)
    prefill: np.ndarray           # answered in set-up (fills the cache)


def _stratified(n: int, inverse_cdf, order_rng) -> np.ndarray:
    """The inverse CDF at the n mid-quantiles, in a seeded order."""
    u = (np.arange(n) + 0.5) / n
    return order_rng.permutation(inverse_cdf(u))


def _zipf_inverse_cdf(num_vertices: int, s: float):
    w = 1.0 / np.arange(1, num_vertices + 1, dtype=np.float64) ** s
    cdf = np.cumsum(w) / w.sum()

    def inv(u):
        return np.minimum(np.searchsorted(cdf, u, side="left"), num_vertices - 1)
    return inv


def _lru_steady(inverse_cdf, capacity: int, stream_rng) -> np.ndarray:
    """What an LRU of ``capacity`` holds after a long stream drawn through
    ``inverse_cdf``: the stream's last ``capacity`` distinct values, least
    recent first (fewer where the stream holds fewer)."""
    newest_first = inverse_cdf(stream_rng.random(64 * capacity))[::-1]
    values, first = np.unique(newest_first, return_index=True)
    return values[np.argsort(first)][:capacity][::-1]


def make_schedule(params: dict, seed: int, linked: np.ndarray, kappa: int,
                  seconds: float, cache_capacity: int = 0) -> Schedule:
    """The schedule of one run; ``linked`` holds the ids of the graph's
    vertices that have an edge, the only ones asked for, and
    ``cache_capacity`` is the service's result cache (``prefill``)."""
    loop = params["loop"]
    order = rng(seed, ORDER_STREAM)
    k = int(params.get("k", 10))
    num_vertices = len(linked)
    if loop == "closed":
        n = min(int(params["max_requests"]), num_vertices - kappa)
        due = None
    elif loop == "open":
        rate = float(params["rate_qps"])
        n = max(1, int(round(rate * seconds)))
        if params.get("arrivals", "poisson") != "poisson":
            raise ValueError(f"unknown arrivals {params['arrivals']!r}")
        gaps = rng(int(params["schedule_seed"]), GAPS_STREAM).exponential(
            1.0 / rate, n)
        due = np.cumsum(gaps) - gaps[0]
    else:
        raise ValueError(f"unknown loop {loop!r}")

    kind = params.get("vertices", "uniform")
    prefill = np.zeros(0, np.int64)
    if kind == "uniform":
        if n + kappa > num_vertices:
            raise ValueError(f"{n} distinct vertices asked of a graph with "
                             f"{num_vertices}")
        perm = order.permutation(num_vertices)
        vertices, warm = perm[:n], perm[n:n + kappa]
    elif kind == "zipf":
        popularity = order.permutation(num_vertices)
        law = _zipf_inverse_cdf(num_vertices, float(params["zipf_s"]))
        vertices = popularity[_stratified(n, law, order)]
        fill = params.get("prefill")
        if fill == "lru_steady":         # also compiles the wave shapes
            prefill = popularity[_lru_steady(
                law, int(cache_capacity),
                rng(int(params["schedule_seed"]), PREFILL_STREAM))]
        elif fill is not None:
            raise ValueError(f"unknown prefill {fill!r}")
        warm = popularity[:0] if len(prefill) else popularity[-kappa:]
    else:
        raise ValueError(f"unknown vertices {kind!r}")
    linked = np.asarray(linked, np.int64)
    return Schedule(loop=loop, precision=params["precision"], k=k,
                    vertices=linked[vertices], due_s=due,
                    clients=int(params.get("clients", 0)),
                    warm=linked[warm], prefill=linked[prefill])
