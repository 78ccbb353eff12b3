"""The fixed-point SpMV's row-prefix reduction.

On a stream sorted by destination, ``spmv_fixed`` sums each row as the
difference of the products' wrap-around uint32 prefix sum at the row's
boundaries (``SortedDst``).  Every test here holds it to the segment-sum
scatter bit for bit, from the kernel up to answers served over a delta."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import COOGraph, Q1_25
from repro.core.ppr import (make_ppr_fixed, make_ppr_fixed_step,
                            personalization_matrix, ppr_step_float)
from repro.core.spmv import ROW_PREFIX_ALIGN, SortedDst, spmv_fixed
from repro.graph_updates import EdgeDelta
from repro.ppr_serving import PPRQuery, PPRService
from repro.ppr_serving.graphs import RegisteredGraph

FMT = Q1_25
V = 300
HUB = 7


def _edges(seed):
    """Random arcs with a hub row, isolated vertices (vertex 0, which the
    pad tail's x = 0 also names, and the last one) and an empty middle
    band."""
    rng = np.random.default_rng(seed)
    src = rng.integers(1, V - 1, 1500)
    dst = rng.integers(1, V - 1, 1500)
    dst[(dst > 100) & (dst < 140)] = HUB           # empty rows 101..139
    dst[:400] = HUB                                 # hub row
    keep = src != dst
    return src[keep], dst[keep]


def _graph(seed=0):
    return COOGraph.from_edges(*_edges(seed), V)


def _reduce_both(rg, p_raw):
    """spmv_fixed on the plain stream and on the registered graph's rows."""
    x, y, _ = rg.device_full()
    val_raw = rg.quantized(FMT)
    rows = rg.device_rows()
    assert isinstance(rows, SortedDst)
    f = jax.jit(lambda xx, p: spmv_fixed(xx, y, val_raw, p, V, FMT))
    return np.asarray(f(x, p_raw)), np.asarray(f(rows, p_raw))


def _pad_tail(rg):
    return rg.graph.num_edges - rg.source.num_edges


@pytest.mark.parametrize("case", ["k1", "k8", "k16", "wrap"])
def test_row_prefix_equals_scatter(case):
    g = _graph()
    rg = RegisteredGraph("g", g, packet=256)
    assert _pad_tail(rg) > 0 and rg.graph.num_edges % ROW_PREFIX_ALIGN == 0
    rng = np.random.default_rng(3)
    if case == "wrap":
        # P near 1.0 in every entry: products ≈ val_raw, so the running sum
        # over the whole stream passes 2^32 several times
        k = 16
        p = rng.integers(FMT.scale - 64, FMT.scale, (V, k), dtype=np.uint64)
    else:
        k = int(case[1:])
        p = rng.integers(0, FMT.scale // 4, (V, k), dtype=np.uint64)
    p_raw = jnp.asarray(p.astype(np.uint32))
    plain, prefix = _reduce_both(rg, p_raw)
    np.testing.assert_array_equal(plain, prefix)
    assert prefix.dtype == np.uint32 and prefix.shape == (V, k)
    indeg = np.bincount(g.x, minlength=V)
    assert (prefix[indeg == 0] == 0).all()          # empty rows sum to 0
    if case == "wrap":
        prod = FMT.mul(rg.quantized(FMT)[:, None],
                       p_raw[rg.device_full()[1]])
        total = np.asarray(prod).astype(np.uint64).sum(0)
        assert (total >= 2 ** 32).all()             # the prefix did wrap


def test_row_prefix_step_lowers_without_a_scatter():
    e = 4096
    spec = jax.ShapeDtypeStruct
    args = (spec((e,), jnp.int32), spec((e,), jnp.uint32),
            spec((V,), jnp.bool_), spec((V, 16), jnp.uint32),
            spec((V, 16), jnp.uint32))
    step = make_ppr_fixed_step(FMT, V, 0.85)
    plain = step.lower(spec((e,), jnp.int32), *args).as_text()
    rows = step.lower(SortedDst(spec((e,), jnp.int32),
                                spec((V + 1,), jnp.int32)), *args).as_text()
    assert "scatter" in plain and "scatter" not in rows


def test_unsorted_stream_is_refused():
    g = _graph()
    order = np.random.default_rng(1).permutation(g.num_edges)
    shuffled = COOGraph(V, g.x[order], g.y[order], g.val[order], g.dangling)
    with pytest.raises(ValueError, match="sorted by destination"):
        RegisteredGraph("g", shuffled, packet=256).device_rows()
    svc = PPRService(kappa=4, iterations=6)
    with pytest.raises(ValueError, match="sorted by destination"):
        svc.register_graph("g", shuffled, formats=["Q1.25"], engine="single")
    assert "g" not in svc.graphs
    svc.register_graph("g", shuffled, formats=[], engine="single")   # float


def _service(g, **kw):
    svc = PPRService(kappa=4, iterations=6, **kw)
    svc.register_graph("g", g, formats=["Q1.25"], engine="single")
    return svc


def _reduce_counts(svc):
    d = svc.telemetry.registry.as_dict()
    return {r: d.get(f"ppr_spmv_reduce_waves_total{{reduce={r}}}", 0)
            for r in ("row_prefix", "scatter")}


def _assert_matches_make_ppr_fixed(recs, g, verts, k=10):
    gp = g.pad_to_packets(256)
    raw = np.zeros(gp.num_edges, np.uint32)
    raw[:g.num_edges] = g.quantized_val(FMT)
    P, _ = make_ppr_fixed(FMT, g.num_vertices, 6, 0.85)(
        jnp.asarray(gp.x), jnp.asarray(gp.y), jnp.asarray(raw),
        jnp.asarray(gp.dangling), jnp.asarray(np.asarray(verts, np.int32)))
    P = np.asarray(P)
    for col, (v, rec) in enumerate(zip(verts, recs)):
        assert rec.source == "wave"
        got = np.round(np.asarray(rec.scores) * FMT.scale).astype(np.uint64)
        np.testing.assert_array_equal(got, P[rec.vertices, col])
        ref = np.delete(P[:, col], v)
        np.testing.assert_array_equal(got, np.sort(ref)[::-1][:k])


def test_service_answers_as_make_ppr_fixed():
    g = _graph()
    svc = _service(g)
    verts = [HUB, 3, 50, 200]
    recs = svc.run_batch([PPRQuery("g", v, k=10, precision="Q1.25")
                          for v in verts])
    _assert_matches_make_ppr_fixed(recs, g, verts)
    assert _reduce_counts(svc) == {"row_prefix": 1, "scatter": 0}


def test_delta_rebuilds_rows_and_matches_a_fresh_registration():
    g = _graph()
    svc = _service(g)
    svc.run_batch([PPRQuery("g", 2, k=10, precision="Q1.25")])
    old_ptr = np.asarray(svc._graphs["g"].device_rows().row_ptr)
    delta = EdgeDelta(add_src=np.array([5, 6, 9, 9]),
                      add_dst=np.array([120, 120, 0, V - 1]),
                      remove_src=g.y[:3].astype(np.int64),
                      remove_dst=g.x[:3].astype(np.int64))
    svc.apply_delta("g", delta)
    merged, _ = delta.apply(g)
    rows = svc._graphs["g"].device_rows()
    want = np.searchsorted(merged.x, np.arange(V + 1))
    np.testing.assert_array_equal(np.asarray(rows.row_ptr), want)
    assert not np.array_equal(old_ptr, want)
    fresh = _service(merged)
    verts = [3, 120, HUB, 9]
    a = svc.run_batch([PPRQuery("g", v, k=10, precision="Q1.25")
                       for v in verts])
    b = fresh.run_batch([PPRQuery("g", v, k=10, precision="Q1.25")
                         for v in verts])
    for ra, rb in zip(a, b):
        assert ra.source == "wave"
        np.testing.assert_array_equal(ra.vertices, rb.vertices)
        np.testing.assert_array_equal(ra.scores, rb.scores)
    _assert_matches_make_ppr_fixed(a, merged, verts)


def test_float_waves_keep_the_scatter():
    g = _graph()
    svc = _service(g)
    verts = [HUB, 3, 50, 200]
    recs = svc.run_batch([PPRQuery("g", v, k=10) for v in verts])
    gp = g.pad_to_packets(256)
    x, y, val = (jnp.asarray(a) for a in (gp.x, gp.y, gp.val))
    Vmat = personalization_matrix(V, jnp.asarray(np.asarray(verts, np.int32)))
    P = Vmat
    for _ in range(6):
        P = ppr_step_float(x, y, val, jnp.asarray(gp.dangling), Vmat, P,
                           num_vertices=V, alpha=0.85)
    P = np.asarray(P)
    for col, rec in enumerate(recs):
        np.testing.assert_array_equal(np.asarray(rec.scores, np.float32),
                                      P[rec.vertices, col])
    assert _reduce_counts(svc) == {"row_prefix": 0, "scatter": 1}
    svc.run_batch([PPRQuery("g", v, k=10, precision="Q1.25") for v in verts])
    assert _reduce_counts(svc) == {"row_prefix": 1, "scatter": 1}
