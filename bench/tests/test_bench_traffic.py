"""The one traffic generator."""
from collections import OrderedDict

import numpy as np
import pytest

from bench.traffic import _lru_steady, _zipf_inverse_cdf, make_schedule, rng

OPEN = {"loop": "open", "rate_qps": 40.0, "arrivals": "poisson",
        "schedule_seed": 0, "clients": 8,
        "vertices": "uniform", "precision": "Q1.25", "k": 10}
ZIPF = {**OPEN, "vertices": "zipf", "zipf_s": 0.99, "prefill": "lru_steady"}
ALL = np.arange(10_000)
CLOSED = {"loop": "closed", "clients": 32, "max_requests": 4096,
          "vertices": "uniform", "precision": "f32", "k": 10}


def test_open_loop_seeds_share_one_schedule():
    a = make_schedule(OPEN, 1, ALL, 16, 20.0)
    b = make_schedule(OPEN, 2**31 + 5, ALL, 16, 20.0)
    assert len(a.due_s) == 800 and np.array_equal(a.due_s, b.due_s)
    assert not np.array_equal(a.vertices, b.vertices)
    gaps = np.diff(a.due_s)
    assert a.due_s[0] == 0.0 and (gaps >= 0).all()
    assert abs(np.mean(gaps) - 1 / 40.0) < 0.003
    c = make_schedule({**OPEN, "schedule_seed": 1}, 1, ALL, 16, 20.0)
    assert not np.array_equal(a.due_s, c.due_s)


def test_uniform_vertices_are_distinct_and_warm_up_is_apart():
    s = make_schedule(OPEN, 3, ALL, 16, 20.0)
    assert len(set(s.vertices.tolist())) == len(s.vertices)
    assert not set(s.warm.tolist()) & set(s.vertices.tolist())
    assert len(s.warm) == 16 and len(s.prefill) == 0


def test_same_seed_same_schedule():
    a, b = (make_schedule(ZIPF, 9, ALL, 16, 10.0, 64) for _ in range(2))
    assert np.array_equal(a.vertices, b.vertices)
    assert np.array_equal(a.due_s, b.due_s)
    assert np.array_equal(a.prefill, b.prefill)


def test_zipf_prefills_the_hottest_and_repeats_them():
    s = make_schedule(ZIPF, 4, ALL, 16, 20.0, 256)
    hot = set(s.prefill.tolist())
    assert len(hot) == len(s.prefill) == 256 and len(s.warm) == 0
    share = np.mean([v in hot for v in s.vertices.tolist()])
    assert 0.4 < share < 0.8
    assert len(set(s.vertices.tolist())) < 0.7 * len(s.vertices)
    # the hottest vertex, the window's most asked, is among them
    values, counts = np.unique(s.vertices, return_counts=True)
    assert values[np.argmax(counts)] in hot


def test_lru_steady_is_what_an_lru_holds_after_the_stream():
    law = _zipf_inverse_cdf(5000, 0.99)
    held = _lru_steady(law, 100, rng(0, 4))
    lru = OrderedDict()
    for v in law(rng(0, 4).random(64 * 100)).tolist():
        lru[v] = None
        lru.move_to_end(v)
        if len(lru) > 100:
            lru.popitem(last=False)
    assert held.tolist() == list(lru)       # least recent first


def test_zipf_with_an_unknown_prefill_is_refused():
    with pytest.raises(ValueError, match="prefill"):
        make_schedule({**ZIPF, "prefill": "hottest"}, 1, ALL, 16, 5.0, 64)


def test_closed_loop_draws_distinct_vertices_for_its_clients():
    s = make_schedule(CLOSED, 5, np.arange(1 << 12), 16, 20.0)
    assert s.due_s is None and s.clients == 32
    assert len(s.vertices) == (1 << 12) - 16
    assert len(set(s.vertices.tolist())) == len(s.vertices)


def test_only_vertices_with_an_edge_are_asked_for():
    linked = np.arange(0, 10_000, 3)
    for params in (OPEN, ZIPF, CLOSED):
        s = make_schedule(params, 6, linked, 16, 20.0)
        asked = np.concatenate([s.vertices, s.warm, s.prefill])
        assert len(asked) and np.isin(asked, linked).all()
