"""Holme-Kim power-law cluster graph (the paper's Table 1 ``pl_*`` graphs).

The model of Holme and Kim, "Growing scale-free networks with tunable
clustering", Phys. Rev. E 65, 026107 (2002), as networkx's
``powerlaw_cluster_graph(n, m, p)`` states it: the graph starts from ``m``
isolated vertices; each arriving vertex draws ``m`` distinct preferential-
attachment targets (uniform picks from the list of edge endpoints so far,
which holds each vertex once per edge it has) and links to the first; each
further link is, with probability ``p``, a triad step (a random neighbour of
the last preferential target that the new vertex is not yet linked to,
closing a triangle) and otherwise the next preferential target, which adds
no edge where a triad step already took it.  The graph is undirected, so
every edge becomes two arcs.

The benchmark owns this code, so that a later change to the program's own
generator cannot move the benchmark's graph.  It returns the arc list; the
harness hands it to the program's ``COOGraph.from_edges``.
"""
from __future__ import annotations

import numpy as np

# the size a CPU rehearsal of a cell on this generator runs at
REHEARSAL = {"num_vertices": 3000}
_CHUNK = 1 << 20
_TRIES = 8      # rejection draws of a triad neighbour before listing them all


def sizes(params: dict):
    """``(num_vertices, arcs)``, the arcs an upper bound: ``m`` edges from
    each arriving vertex, both ways.  The graph is made on the host, so
    there is no ``device_program``."""
    n, m = int(params["num_vertices"]), int(params["m"])
    return n, 2 * m * (n - m)


def generate(params: dict, seed: int):
    """``(num_vertices, src, dst)``: int64 host arrays of the graph's arcs,
    each undirected edge in both directions, no loops or repeats."""
    n = int(params["num_vertices"])
    m = int(params["m"])
    p = float(params["p_triad"])
    if not 1 <= m < n:
        raise ValueError(f"need 1 <= m < n, got m={m}, n={n}")
    rng = np.random.default_rng(seed)
    draws: list = []

    def uniform() -> float:
        if not draws:
            draws.extend(rng.random(_CHUNK).tolist())
        return draws.pop()

    adj: list = [[] for _ in range(n)]
    pool = list(range(m))           # edge endpoints, one entry per edge end
    us: list = []
    vs: list = []
    for source in range(m, n):
        picks: list = []
        while len(picks) < m:       # m distinct preferential targets
            t = pool[int(uniform() * len(pool))]
            if t not in picks:
                picks.append(t)
        linked = set()

        def link(t: int) -> None:
            adj[source].append(t)
            adj[t].append(source)
            linked.add(t)
            pool.append(t)
            us.append(source)
            vs.append(t)

        target = picks.pop()
        link(target)
        for _ in range(m - 1):
            if uniform() < p:
                nbr = _triad_neighbour(adj[target], linked, source, uniform)
                if nbr is not None:
                    link(nbr)
                    continue
            target = picks.pop()
            if target in linked:    # a triad step took it: no second edge
                pool.append(target)
            else:
                link(target)
        pool.extend([source] * m)
    u = np.asarray(us, np.int64)
    v = np.asarray(vs, np.int64)
    return n, np.concatenate([u, v]), np.concatenate([v, u])


def _triad_neighbour(nbrs: list, linked: set, source: int, uniform):
    """A uniform pick among ``nbrs`` that are neither ``source`` nor in
    ``linked``, or None where there is none."""
    for _ in range(_TRIES):
        c = nbrs[int(uniform() * len(nbrs))]
        if c != source and c not in linked:
            return c
    free = [c for c in nbrs if c != source and c not in linked]
    return free[int(uniform() * len(free))] if free else None
