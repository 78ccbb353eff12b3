"""The benchmark's graph generators."""
import numpy as np
import pytest

from bench.generators import holme_kim, kronecker

KRON = {"scale": 10, "edge_factor": 16, "initiator": [0.57, 0.19, 0.19]}


@pytest.fixture(scope="module")
def kron_small():
    return kronecker.generate(KRON, 2**31 + 7)


def test_kronecker_is_seeded(kron_small):
    n, src, dst = kron_small
    n2, src2, dst2 = kronecker.generate(KRON, 2**31 + 7)
    assert n == n2 and np.array_equal(src, src2) and np.array_equal(dst, dst2)
    _, src3, _ = kronecker.generate(KRON, 2**31 + 8)
    assert len(src3) != len(src) or not np.array_equal(src3, src)


def test_kronecker_sizes(kron_small):
    n, src, dst = kron_small
    assert n == 1 << 10
    arcs = 2 * (16 << 10)           # each generated tuple, both ways
    # R-MAT at this scale repeats a good share of its edges; all are distinct
    # after dedup, none is a self-loop, and every id is a vertex
    assert 0.5 * arcs < len(src) < arcs
    assert src.min() >= 0 and max(src.max(), dst.max()) < n
    assert not (src == dst).any()
    assert len(np.unique(src * n + dst)) == len(src)


def _symmetric(n, src, dst):
    return np.array_equal(np.sort(src * n + dst), np.sort(dst * n + src))


def test_kronecker_graph_is_undirected(kron_small):
    """Graph500's kernel 1 makes an undirected graph of the tuples."""
    assert _symmetric(*kron_small)


def test_kronecker_is_sorted_by_destination_then_source(kron_small):
    _, src, dst = kron_small
    key = dst * (1 << 10) + src
    assert (np.diff(key) > 0).all()


def test_kronecker_degrees_are_skewed(kron_small):
    n, src, dst = kron_small
    indeg = np.bincount(dst, minlength=n)
    assert indeg.max() > 20 * indeg.mean()


def _clustering(n, src, dst):
    """networkx's average clustering of the undirected graph."""
    import networkx as nx

    g = nx.Graph()
    g.add_nodes_from(range(n))
    g.add_edges_from(zip(src.tolist(), dst.tolist()))
    return g, nx.average_clustering(g)


@pytest.mark.parametrize("n,m", [(500, 3), (3000, 10)])
def test_holme_kim_is_the_programs_generator(n, m):
    """The benchmark's generator is the Holme-Kim model as its reference
    implementation, networkx's ``powerlaw_cluster_graph``, states it (the
    program's own generator closes no triangle, so the benchmark no longer
    follows it): an undirected simple graph with networkx's edge count and,
    with triad steps, networkx's clustering."""
    import networkx as nx

    v, src, dst = holme_kim.generate({"num_vertices": n, "m": m,
                                      "p_triad": 0.5}, 5)
    assert v == n and _symmetric(n, src, dst) and not (src == dst).any()
    assert len(np.unique(src * n + dst)) == len(src)
    g, mine = _clustering(n, src, dst)
    theirs = [nx.powerlaw_cluster_graph(n, m, 0.5, seed=s) for s in (1, 2, 3)]
    edges = [h.number_of_edges() for h in theirs]
    assert min(edges) - 5 <= g.number_of_edges() <= max(edges) + 5
    ref = np.mean([nx.average_clustering(h) for h in theirs])
    assert abs(mine - ref) < 0.25 * ref
    assert min(d for _, d in g.degree()) >= 1


def test_holme_kim_triad_steps_close_triangles():
    n = 3000
    _, plain = _clustering(*holme_kim.generate(
        {"num_vertices": n, "m": 4, "p_triad": 0.0}, 8))
    _, triads = _clustering(*holme_kim.generate(
        {"num_vertices": n, "m": 4, "p_triad": 0.8}, 8))
    assert triads > 5 * plain


def test_holme_kim_is_seeded():
    params = {"num_vertices": 800, "m": 4, "p_triad": 0.1}
    a, b, c = (holme_kim.generate(params, s) for s in (2**31 + 1,
                                                        2**31 + 1, 3))
    assert all(np.array_equal(x, y) for x, y in zip(a[1:], b[1:]))
    assert not np.array_equal(a[1], c[1])
