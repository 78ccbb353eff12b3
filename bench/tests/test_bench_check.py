"""The float64 reference and the comparison that decides ``correct``."""
import numpy as np
import pytest

from bench import check
from bench.generators import holme_kim
from bench.load import Request
from bench.reference import FixedReference, Reference, ppr_bf16

K = 10


@pytest.fixture(scope="module")
def graph():
    return holme_kim.generate({"num_vertices": 2000, "m": 5, "p_triad": 0.1}, 3)


@pytest.fixture(scope="module")
def ref(graph):
    n, src, dst = graph
    return Reference(n, src, dst, 0.85, 10)


def _answer(ref, q, scores=None, precision="Q1.25"):
    col = (ref.scores([q])[:, 0] if scores is None else scores).copy()
    col[q] = -np.inf
    top = np.lexsort((np.arange(ref.n), -col))[:K]
    return Request(int(q), due=0.0, status=200, payload={
        "graph": "g", "vertex": int(q), "k": K, "precision": precision,
        "recommendations": [{"vertex": int(v), "score": float(col[v])}
                            for v in top]})


def test_reference_is_the_programs_float64_reference(graph, ref):
    from repro.core.coo import COOGraph
    from repro.graphs import ppr_reference

    n, src, dst = graph
    seeds = [0, 17, 1999]
    theirs = ppr_reference(COOGraph.from_edges(src, dst, n), seeds,
                           iterations=10)
    assert np.allclose(ref.scores(seeds), theirs, rtol=0, atol=1e-7)


def test_exact_answers_read_zero(ref):
    reqs = [_answer(ref, q) for q in (1, 5, 77)]
    n = check.compare(reqs, ref, graph="g", precision_key="Q1.25", k=K,
                      seed=1, max_answers=10)
    assert n == {"unanswered": 0, "malformed": 0, "err_max": 0.0,
                 "err_mean": 0.0, "compared": 3}


def test_lower_precision_reads_higher(graph, ref):
    n, src, dst = graph
    qs = [2, 40, 600, 1500]
    bf = ppr_bf16(n, src, dst, qs, 0.85, 10)
    good = check.compare([_answer(ref, q) for q in qs], ref, graph="g",
                         precision_key="f32", k=K, seed=1, max_answers=10)
    bad = check.compare([_answer(ref, q, bf[:, i], "f32")
                         for i, q in enumerate(qs)], ref, graph="g",
                        precision_key="f32", k=K, seed=1, max_answers=10)
    assert bad["err_max"] > 1e-4 > good["err_max"]


def _mutate(req, how):
    p = req.payload
    recs = p["recommendations"]
    if how == "self":
        recs[0]["vertex"] = req.vertex
    elif how == "repeat":
        recs[1]["vertex"] = recs[0]["vertex"]
    elif how == "short":
        recs.pop()
    elif how == "rising":
        recs[0]["score"], recs[1]["score"] = recs[1]["score"], recs[0]["score"] + 1
    elif how == "echo":
        p["vertex"] = req.vertex + 1
    elif how == "precision":
        p["precision"] = "f32"
    elif how == "range":
        recs[3]["vertex"] = 10**9
    elif how == "score":
        recs[2]["score"] = None
    return req


@pytest.mark.parametrize("how", ["self", "repeat", "short", "rising", "echo",
                                 "precision", "range", "score"])
def test_malformed_answers_are_counted(ref, how):
    reqs = [_answer(ref, 3), _mutate(_answer(ref, 4), how)]
    n = check.compare(reqs, ref, graph="g", precision_key="Q1.25", k=K,
                      seed=1, max_answers=10)
    assert n["malformed"] == 1 and n["compared"] == 1


def test_a_wrong_vertex_or_order_reads_large(ref):
    swapped = _answer(ref, 9)
    recs = swapped.payload["recommendations"]
    recs[0]["vertex"], recs[5]["vertex"] = recs[5]["vertex"], recs[0]["vertex"]
    n = check.compare([swapped], ref, graph="g", precision_key="Q1.25", k=K,
                      seed=1, max_answers=10)
    assert n["malformed"] == 0 and n["err_max"] > 1e-3


def test_unanswered_and_server_errors_count(ref):
    """No answer, a server error and a request shed by admission (429) are
    all requests not served."""
    reqs = [Request(1, 0.0), Request(2, 0.0, status=500, payload={}),
            Request(3, 0.0, status=429, payload={})]
    n = check.compare(reqs, ref, graph="g", precision_key="Q1.25", k=K,
                      seed=1, max_answers=10)
    assert n["unanswered"] == 3 and n["compared"] == 0
    ok, shown = check.verdict(n, {"unanswered": 0, "err_max": 1.0})
    assert not ok and set(shown) == {"unanswered", "err_max"}


def test_sample_is_drawn_from_the_seed():
    items = list(range(100))
    a, b = check.sample(items, 7, 10), check.sample(items, 7, 10)
    assert a == b and len(a) == 10 and a != check.sample(items, 8, 10)
    assert check.sample(items[:5], 7, 10) == items[:5]


@pytest.mark.parametrize("frac_bits", [25, 23])
def test_fixed_reference_is_the_programs_datapath_bit_for_bit(graph, frac_bits):
    """The plain integer recurrence equals the program's fixed-point path
    (an independent implementation of the same truncating arithmetic)."""
    import jax.numpy as jnp

    from repro.core.coo import COOGraph
    from repro.core.fixed_point import QFormat
    from repro.core.ppr import make_ppr_fixed

    n, src, dst = graph
    fmt = QFormat(1, frac_bits)
    g = COOGraph.from_edges(src, dst, n)
    seeds = np.asarray([0, 3, 999, 1998])
    run = make_ppr_fixed(fmt, n, 10, 0.85)
    P, _ = run(jnp.asarray(g.x), jnp.asarray(g.y),
               jnp.asarray(g.quantized_val(fmt)), jnp.asarray(g.dangling),
               jnp.asarray(seeds, jnp.int32))
    ref = FixedReference(n, src, dst, 0.85, 10, frac_bits)
    mine = np.stack(ref.map_columns(seeds, lambda _, c: c), axis=1)
    assert np.array_equal(mine, np.asarray(P).astype(np.int64))


def _exact_answer(ref, q):
    col = ref.column(q)
    col[q] = -1
    top = np.lexsort((np.arange(ref.n), -col))[:K]
    return Request(int(q), due=0.0, status=200, payload={
        "graph": "g", "vertex": int(q), "k": K, "precision": "Q1.25",
        "recommendations": [{"vertex": int(v), "score": col[v] / ref.scale}
                            for v in top]})


def test_fixed_point_answers_compare_exactly(graph):
    n, src, dst = graph
    ref = FixedReference(n, src, dst, 0.85, 10, 25)
    reqs = [_exact_answer(ref, q) for q in (4, 8, 15)]
    kw = dict(graph="g", precision_key="Q1.25", k=K, seed=1, max_answers=10)
    assert check.compare(reqs, ref, **kw) == {
        "unanswered": 0, "malformed": 0, "compared": 3, "mismatched": 0}
    recs = reqs[1].payload["recommendations"]
    recs[9]["score"] -= 1 / ref.scale           # one LSB off at the 10th
    assert check.compare(reqs, ref, **kw)["mismatched"] == 1


def test_a_shed_request_is_timed_at_the_give_up_time():
    """A 429 comes back fast; it counts at the give-up time, as a request
    never answered does, so shedding cannot read as low latency."""
    from bench.harness import GIVE_UP_S, _open_window

    served = Request(1, due=0.0, sent=0.0, recv=0.5, status=200, payload={})
    shed = Request(2, due=1.0, sent=1.0, recv=1.01, status=429, payload={})
    lost = Request(3, due=1.5, sent=1.5)
    out = _open_window([served, shed, lost], 0.0)
    give_up = 1.5 + GIVE_UP_S            # the last send, plus the wait
    assert out["latencies_s"] == [0.5, give_up - 1.0, give_up - 1.5]
    assert out["in_window"] == [served]
