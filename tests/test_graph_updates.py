"""Dynamic graph updates: host-side merge equivalence vs from-scratch builds,
epoch-versioned apply_delta with scoped invalidation, incremental
requantization, warm-start seeding, the async prefetcher, and the mesh-sharded
delta path (subprocess, per run-book)."""
import os
import subprocess
import sys
import textwrap
from collections import Counter

import numpy as np
import pytest

from repro.core import COOGraph, format_for_bits, merge_edge_delta
from repro.graph_updates import (
    EdgeDelta,
    WarmStartStore,
    localized_delta,
    random_delta,
)
from repro.graphs import erdos_renyi, holme_kim_powerlaw
from repro.ppr_serving import PPRQuery, PPRService, PrefetchConfig, Prefetcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def graph():
    return holme_kim_powerlaw(400, m=4, seed=2)


def _oracle_merge(g: COOGraph, d: EdgeDelta) -> COOGraph:
    """Independent merge: edge multiset rebuild + from_edges from scratch."""
    c = Counter(zip(g.y.tolist(), g.x.tolist()))
    for s, t in zip(d.remove_src.tolist(), d.remove_dst.tolist()):
        c[(s, t)] -= 1
        assert c[(s, t)] >= 0, "oracle: removal of missing edge"
    for s, t in zip(d.add_src.tolist(), d.add_dst.tolist()):
        c[(s, t)] += 1
    src, dst = [], []
    for (s, t), n in c.items():
        src += [s] * n
        dst += [t] * n
    v = d.new_num_vertices or g.num_vertices
    return COOGraph.from_edges(np.asarray(src, np.int64),
                               np.asarray(dst, np.int64), v)


def assert_graphs_bit_identical(a: COOGraph, b: COOGraph):
    assert a.num_vertices == b.num_vertices
    np.testing.assert_array_equal(a.x, b.x)
    np.testing.assert_array_equal(a.y, b.y)
    # float32 val compared bitwise: 1/outdeg must reproduce exactly
    np.testing.assert_array_equal(a.val.view(np.uint32), b.val.view(np.uint32))
    np.testing.assert_array_equal(a.dangling, b.dangling)


# ---------------------------------------------------------------------------
# merge_edge_delta: bit-identical to a from-scratch from_edges build
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed,grow", [(0, 0), (1, 0), (2, 3), (3, 7)])
def test_merge_matches_from_scratch_build(graph, seed, grow):
    rng = np.random.default_rng(seed)
    d = random_delta(graph, rng, n_add=25, n_remove=12, grow=grow)
    merged, info = d.apply(graph)
    assert_graphs_bit_identical(merged, _oracle_merge(graph, d))
    # info maps surviving edges old→new consistently
    np.testing.assert_array_equal(merged.x[info.new_pos_of_kept],
                                  graph.x[info.kept_old_idx])
    np.testing.assert_array_equal(merged.y[info.new_pos_of_kept],
                                  graph.y[info.kept_old_idx])
    # unchanged entries kept their val bits without renormalization
    kept_unchanged = info.new_pos_of_kept[
        ~info.changed_mask[info.new_pos_of_kept]]
    assert kept_unchanged.size > 0
    # every added edge's slot is marked changed
    assert info.changed_mask.sum() >= d.num_added


def test_merge_removal_can_empty_a_source_to_dangling():
    g = COOGraph.from_edges(np.array([0, 0, 1]), np.array([1, 2, 2]), 4)
    d = EdgeDelta(remove_src=[0, 0], remove_dst=[1, 2])
    merged, _ = d.apply(g)
    assert merged.dangling[0]
    assert_graphs_bit_identical(merged, _oracle_merge(g, d))


def test_merge_multi_edge_multiplicity():
    src = np.array([0, 0, 0, 1])
    dst = np.array([1, 1, 2, 0])
    g = COOGraph.from_edges(src, dst, 3)
    merged, _ = EdgeDelta(remove_src=[0], remove_dst=[1]).apply(g)
    assert merged.num_edges == 3                  # one instance removed
    with pytest.raises(ValueError, match="more times than it exists"):
        EdgeDelta(remove_src=[0, 0, 0], remove_dst=[1, 1, 1]).apply(g)


def test_merge_validation_errors(graph):
    v = graph.num_vertices
    with pytest.raises(ValueError, match="shrinks"):
        merge_edge_delta(graph, [0], [1], [], [], new_num_vertices=v - 1)
    with pytest.raises(ValueError, match="out of range"):
        EdgeDelta(add_src=[v + 5], add_dst=[0]).apply(graph)
    with pytest.raises(ValueError, match="out of range"):
        EdgeDelta(remove_src=[v], remove_dst=[0]).apply(graph)
    with pytest.raises(ValueError, match="length mismatch"):
        EdgeDelta(add_src=[1, 2], add_dst=[3])


def test_growth_only_delta_adds_dangling_vertices(graph):
    d = EdgeDelta(new_num_vertices=graph.num_vertices + 5)
    merged, info = d.apply(graph)
    assert merged.num_vertices == graph.num_vertices + 5
    assert merged.dangling[-5:].all()
    assert merged.num_edges == graph.num_edges
    assert not info.changed_mask.any()


def test_affected_frontier_touched_plus_in_neighbors():
    # 0→1, 2→1, 3→2: touching vertex 1 must pull in-neighbors {0, 2}
    g = COOGraph.from_edges(np.array([0, 2, 3]), np.array([1, 1, 2]), 5)
    d = EdgeDelta(add_src=[1], add_dst=[4])
    np.testing.assert_array_equal(d.affected_frontier(g), [0, 1, 2, 4])


# ---------------------------------------------------------------------------
# apply_delta: cold-query equivalence vs full re-registration + recompute
# ---------------------------------------------------------------------------
def _raw_scores(rec, fmt):
    raw = np.asarray(rec.scores) * fmt.scale
    out = raw.round().astype(np.uint64)
    np.testing.assert_allclose(raw, out, atol=0)     # exactly representable
    return out


@pytest.mark.parametrize("grow", [0, 3])
def test_apply_delta_cold_query_equivalence_single_device(graph, grow):
    """Acceptance: apply_delta + cold query == fresh registration of the
    merged graph — bit-identical raw uint32 on the fixed path, exact float."""
    rng = np.random.default_rng(7)
    d = random_delta(graph, rng, n_add=18, n_remove=9, grow=grow)
    fmt = format_for_bits(26)

    svc = PPRService(kappa=4, iterations=8)
    svc.register_graph("g", graph, formats=[26])
    svc.serve([PPRQuery("g", v, k=10, precision=26) for v in (1, 5, 9, 13)])
    svc.apply_delta("g", d)

    merged, _ = d.apply(graph)
    fresh = PPRService(kappa=4, iterations=8)
    fresh.register_graph("g", merged, formats=[26])

    # device-side derived state is bit-identical to a from-scratch build
    rg, rf = svc._graphs["g"], fresh._graphs["g"]
    np.testing.assert_array_equal(np.asarray(rg.quantized(fmt)),
                                  np.asarray(rf.quantized(fmt)))
    np.testing.assert_array_equal(np.asarray(rg.val), np.asarray(rf.val))
    np.testing.assert_array_equal(np.asarray(rg.dangling),
                                  np.asarray(rf.dangling))

    probe = [2, 6, graph.num_vertices - 1]
    if grow:
        probe.append(graph.num_vertices + grow - 1)   # a grown vertex serves
    for v in probe:
        a = svc.serve([PPRQuery("g", v, k=10, precision=26)])[0]
        b = fresh.serve([PPRQuery("g", v, k=10, precision=26)])[0]
        assert a.source == "wave"                     # cold: no stale cache
        np.testing.assert_array_equal(a.vertices, b.vertices)
        np.testing.assert_array_equal(_raw_scores(a, fmt), _raw_scores(b, fmt))
        af = svc.serve([PPRQuery("g", v, k=10)])[0]
        bf = fresh.serve([PPRQuery("g", v, k=10)])[0]
        np.testing.assert_array_equal(af.vertices, bf.vertices)
        np.testing.assert_array_equal(af.scores, bf.scores)


def test_incremental_requantization_all_formats(graph):
    """Only changed val entries go through the quantizer, yet every
    pre-registered format's raw array equals a from-scratch quantization."""
    rng = np.random.default_rng(3)
    svc = PPRService(kappa=2, iterations=2)
    svc.register_graph("g", graph, formats=[20, 26])
    d = random_delta(graph, rng, n_add=30, n_remove=15)
    svc.apply_delta("g", d)
    merged, _ = d.apply(graph)
    rg = svc._graphs["g"]
    for bits in (20, 26):
        fmt = format_for_bits(bits)
        np.testing.assert_array_equal(rg._quantized_host[fmt],
                                      merged.quantized_val(fmt))


def test_epoch_bumps_and_cache_keys_do_not_alias(graph):
    svc = PPRService(kappa=1, iterations=4)
    svc.register_graph("g", graph)
    assert svc._graphs["g"].epoch == 0
    k0 = svc._cache_key(PPRQuery("g", 1, k=5), "f32")
    svc.apply_delta("g", EdgeDelta(add_src=[1], add_dst=[2]))
    assert svc._graphs["g"].epoch == 1
    k1 = svc._cache_key(PPRQuery("g", 1, k=5), "f32")
    assert k0 != k1 and k0[1] == 0 and k1[1] == 1


# ---------------------------------------------------------------------------
# scoped invalidation: frontier entries drop, the rest keep serving
# ---------------------------------------------------------------------------
def test_scoped_invalidation_drops_strictly_fewer_than_whole_graph(graph):
    svc = PPRService(kappa=8, iterations=5)
    svc.register_graph("g", graph, formats=[26])
    rng = np.random.default_rng(0)
    verts = rng.choice(graph.num_vertices, size=32, replace=False)
    svc.serve([PPRQuery("g", int(v), k=10, precision=26) for v in verts])
    cached = len(svc.cache)
    assert cached == 32
    d = localized_delta(graph, rng, n_add=2, n_remove=1)
    frontier = set(int(v) for v in d.affected_frontier(graph))
    report = svc.apply_delta("g", d)
    assert report["cache_dropped"] < cached            # strictly fewer
    assert report["cache_dropped"] + report["cache_retained"] == cached
    t = svc.telemetry_summary()
    assert t["deltas_applied"] == 1
    assert t["scoped_cache_retained"] == report["cache_retained"]
    # retained entries serve from cache at the new epoch; frontier recomputes
    hits = waves = 0
    for v in verts:
        rec = svc.serve([PPRQuery("g", int(v), k=10, precision=26)])[0]
        if int(v) in frontier:
            assert rec.source == "wave"
            waves += 1
        else:
            assert rec.source == "cache"
            hits += 1
    assert hits == report["cache_retained"]
    assert waves == report["cache_dropped"]


def test_scoped_purge_of_pending_queries(graph):
    """Pending frontier queries drop; survivors move to the new epoch's wave
    keys with their admission budgets intact and launch on the new graph."""
    svc = PPRService(kappa=8, iterations=4)
    svc.register_graph("g", graph)
    d = localized_delta(graph, np.random.default_rng(1), n_add=2, n_remove=1)
    frontier = set(int(v) for v in d.affected_frontier(graph))
    in_f = sorted(frontier)[0]
    out_f = next(v for v in range(graph.num_vertices) if v not in frontier)
    fut_in = svc.submit(PPRQuery("g", in_f, k=5))
    fut_out = svc.submit(PPRQuery("g", out_f, k=5))
    assert not fut_in.done() and not fut_out.done()
    report = svc.apply_delta("g", d)
    assert report["pending_dropped"] == 1
    assert report["pending_requeued"] == 1
    assert svc.scheduler.pending() == 1
    # the frontier future is rejected descriptively; the survivor stays pending
    assert fut_in.done() and fut_in.exception() is not None
    assert not fut_out.done()
    recs = svc.drain()
    assert len(recs) == 1 and recs[0].query.vertex == out_f
    assert fut_out.result() is recs[0]
    # the survivor computed on the NEW topology and cached at the new epoch
    assert svc.serve([PPRQuery("g", out_f, k=5)])[0].source == "cache"


def test_autotune_windows_decay_not_reset_on_delta(graph):
    svc = PPRService(kappa=2, iterations=3)
    svc.register_graph("g", graph)
    est = svc.controller.estimator
    for _ in range(8):
        est.record("g", "Q1.25", 0.97)
    svc.apply_delta("g", EdgeDelta(add_src=[1], add_dst=[2]))
    assert est.samples("g", "Q1.25") == 4          # halved, newest kept
    svc.register_graph("g", graph)                 # re-registration still resets
    assert est.samples("g", "Q1.25") == 0


# ---------------------------------------------------------------------------
# warm start
# ---------------------------------------------------------------------------
def test_warm_start_store_lru_and_grow():
    ws = WarmStartStore(capacity_per_graph=2)
    ws.put("g", 1, "f32", np.ones(4, np.float32))
    ws.put("g", 2, "f32", np.ones(4, np.float32))
    assert ws.get("g", 1, "f32") is not None       # refresh 1 → 2 oldest
    ws.put("g", 3, "f32", np.ones(4, np.float32))
    assert ws.get("g", 2, "f32") is None
    assert ws.stats()["evictions"] == 1
    ws.grow("g", 6)
    assert ws.get("g", 1, "f32").shape == (6,)
    assert ws.get("g", 1, "f32")[4:].sum() == 0
    assert ws.drop_graph("g") == 2 and len(ws) == 0


def test_warm_start_saves_iterations_after_delta(graph):
    svc = PPRService(kappa=2, iterations=60, early_exit=True, warm_start=True)
    svc.register_graph("g", graph, formats=[26])
    verts = [3, 9]
    svc.serve([PPRQuery("g", v, k=5, precision=26) for v in verts])
    t0 = svc.telemetry_summary()
    assert t0["warm_start_waves"] == 0             # first wave is cold
    d = EdgeDelta(add_src=verts, add_dst=[50, 60])
    svc.apply_delta("g", d)
    recs = svc.serve([PPRQuery("g", v, k=5, precision=26) for v in verts])
    assert all(r.source == "wave" for r in recs)   # frontier invalidated them
    t1 = svc.telemetry_summary()
    assert t1["warm_start_waves"] == 1
    assert t1["warm_start_columns"] == 2
    # warm results match a cold service on the same merged graph: identical
    # ranking; scores within a few LSBs of quantization noise (the absorbing
    # state reached from a warm seed may differ from the cold trajectory's by
    # trailing bits — the shadow estimator keeps scoring either)
    merged, _ = d.apply(graph)
    cold = PPRService(kappa=2, iterations=60, early_exit=True)
    cold.register_graph("g", merged, formats=[26])
    fmt = format_for_bits(26)
    for r, rc in zip(recs, cold.serve(
            [PPRQuery("g", v, k=5, precision=26) for v in verts])):
        np.testing.assert_array_equal(r.vertices, rc.vertices)
        np.testing.assert_allclose(r.scores, rc.scores, rtol=0,
                                   atol=4 * fmt.resolution)


def test_warm_start_disabled_keeps_cold_key_and_no_store(graph):
    svc = PPRService(kappa=1, iterations=4)
    assert svc._warm is None
    key = svc._cache_key(PPRQuery("g", 0, k=5), "f32")
    warm = PPRService(kappa=1, iterations=4, warm_start=True)
    assert key != warm._cache_key(PPRQuery("g", 0, k=5), "f32")


# ---------------------------------------------------------------------------
# prefetcher (satellite: ROADMAP async-prefetch follow-on)
# ---------------------------------------------------------------------------
def test_prefetch_warms_hot_vertices_on_idle_pump(graph):
    svc = PPRService(kappa=2, iterations=4,
                     prefetch=PrefetchConfig(top_n=4, k=5, max_per_pump=4,
                                             min_count=2))
    svc.register_graph("g", graph, formats=[26])
    for _ in range(2):
        svc.serve([PPRQuery("g", 3, k=5, precision="auto"),
                   PPRQuery("g", 7, k=5, precision="auto")])
    # hot vertices are already cached by real traffic → idle pump issues none
    # for them, and returns no synthetic recommendations either way
    before = svc.telemetry_summary()["prefetch_issued"]
    assert svc.pump() == []
    # cold-but-hot vertex: make 11 hot via traffic, then invalidate its entry
    for _ in range(2):
        svc.serve([PPRQuery("g", 11, k=5, precision="auto")])
    key = [k for k in svc.cache._store if k[2] == 11]
    assert key
    svc.cache.invalidate(lambda k: k[2] == 11)
    assert svc.pump() == []                        # idle pump prefetches it
    t = svc.telemetry_summary()
    assert t["prefetch_issued"] > before
    hits0 = t["lru_hits"]
    rec = svc.serve([PPRQuery("g", 11, k=5, precision="auto")])[0]
    assert rec.source == "cache"                   # warmed-hit through lru_*
    assert svc.telemetry_summary()["lru_hits"] == hits0 + 1


def test_prefetch_rewarms_delta_invalidated_hot_vertices(graph):
    svc = PPRService(kappa=2, iterations=4,
                     prefetch=PrefetchConfig(top_n=2, k=5, max_per_pump=4,
                                             min_count=2))
    svc.register_graph("g", graph, formats=[26])
    for _ in range(3):
        svc.serve([PPRQuery("g", 3, k=5, precision="auto")])
    d = EdgeDelta(add_src=[3], add_dst=[200])      # 3 is in its own frontier
    report = svc.apply_delta("g", d)
    assert report["cache_dropped"] >= 1
    assert svc.telemetry_summary()["prefetch_rewarms_queued"] == 1
    assert svc.pump() == []                        # re-warm fires, returns none
    rec = svc.serve([PPRQuery("g", 3, k=5, precision="auto")])[0]
    assert rec.source == "cache"


def test_prefetch_rewarms_explicit_precision_traffic_under_its_own_key(graph):
    """Regression: re-warm used to issue only at the controller's resolved
    rung, so hot entries from explicit-precision traffic were re-warmed under
    a key real traffic never probes.  The prefetcher now uses the vertex's
    last real (k, precision)."""
    svc = PPRService(kappa=2, iterations=4,
                     prefetch=PrefetchConfig(top_n=2, k=10, max_per_pump=4,
                                             min_count=2))
    svc.register_graph("g", graph, formats=[20])
    for _ in range(3):                                 # hot at explicit Q1.19
        svc.serve([PPRQuery("g", 3, k=7, precision=20)])
    svc.apply_delta("g", EdgeDelta(add_src=[3], add_dst=[200]))
    assert svc.pump() == []                            # idle pump re-warms
    rec = svc.serve([PPRQuery("g", 3, k=7, precision=20)])[0]
    assert rec.source == "cache" and rec.precision == "Q1.19"


def test_prefetch_rewarm_queue_survives_max_per_pump(graph):
    """Regression: candidates() used to clear the whole re-warm queue even
    when the per-pump cap let only a few issue — the overflow now waits for
    the next idle pump instead of being lost."""
    svc = PPRService(kappa=2, iterations=4,
                     prefetch=PrefetchConfig(top_n=2, k=5, max_per_pump=2,
                                             min_count=1))
    svc.register_graph("g", graph, formats=[26])
    hot = [3, 7, 11, 15]
    for v in hot:
        svc.serve([PPRQuery("g", v, k=5, precision="auto")])
    svc.prefetcher.note_invalidated("g", hot)
    svc.cache.invalidate(lambda k: True)
    assert svc.pump() == []                            # warms first 2 only
    assert svc.telemetry_summary()["prefetch_rewarms_pending"] == 2
    assert svc.pump() == []                            # next idle pump: rest
    assert svc.telemetry_summary()["prefetch_rewarms_pending"] == 0
    for v in hot:
        assert svc.serve([PPRQuery("g", v, k=5, precision="auto")])[0] \
            .source == "cache"


def test_prefetch_results_never_returned_but_real_riders_are(graph):
    """A real pending query sharing the prefetch wave's key rides along and
    IS returned; the synthetic queries are not."""
    svc = PPRService(kappa=4, iterations=4, max_wait=100.0,
                     prefetch=PrefetchConfig(top_n=2, k=5, max_per_pump=2,
                                             min_count=1))
    svc.register_graph("g", graph, formats=[26])
    svc.serve([PPRQuery("g", 5, k=5, precision="auto")])   # makes 5 "hot"
    svc.cache.invalidate(lambda k: True)
    # a real query waits in the queue (max_wait keeps it pending)...
    assert not svc.submit(PPRQuery("g", 5, k=5, precision="auto")).done()
    # ...until the idle pump's prefetch flush takes its key's queue along
    recs = svc.pump()
    assert [r.query.prefetch for r in recs] == [False]
    assert recs[0].query.vertex == 5 and recs[0].source == "wave"


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_prefetcher_demand_decay_unit_clock_injected():
    """counts halve per half-life (clock injected); fully-cooled entries are
    pruned; no configured half-life means the legacy cumulative counts."""
    clk = FakeClock()
    p = Prefetcher(PrefetchConfig(half_life_s=10.0), time_fn=clk)
    counts = {1: 8.0, 2: 0.08}
    last_seen = {1: (10, "Q1.25"), 2: (5, "f32")}
    p.decay_demand("g", counts, last_seen=last_seen)   # no time elapsed
    assert counts == {1: 8.0, 2: 0.08}
    clk.t = 10.0
    p.decay_demand("g", counts, last_seen=last_seen)   # exactly one half-life
    assert counts[1] == pytest.approx(4.0)
    assert 2 not in counts                 # cooled below the floor → pruned
    assert last_seen == {1: (10, "Q1.25")}  # (k, pkey) map pruned in lockstep
    clk.t = 30.0
    p.decay_demand("g", counts)            # two more half-lives
    assert counts[1] == pytest.approx(1.0)
    # out-of-order `now` never rewinds the stamp and over-ages later decays
    p.decay_demand("g", counts, now=5.0)
    assert counts[1] == pytest.approx(1.0)
    p.decay_demand("g", counts, now=40.0)  # one half-life since t=30, not 35
    assert counts[1] == pytest.approx(0.5)
    # a graph never decayed before ages from the prefetcher's construction
    # stamp, so the FIRST idle poll after a quiet stretch already decays
    clk.t = 0.0
    cold = Prefetcher(PrefetchConfig(half_life_s=10.0), time_fn=clk)
    stale = {7: 8.0}
    clk.t = 30.0
    cold.decay_demand("h", stale)          # three half-lives since construction
    assert stale[7] == pytest.approx(1.0)
    # decay state is per graph: "h" ages from p's construction stamp (t=0 →
    # clk.t=30, three half-lives), not from "g"'s later stamp at t=40
    other = {5: 8.0}
    p.decay_demand("h", other)
    assert other == {5: pytest.approx(1.0)}
    p.drop_graph("g")
    assert "g" not in p._last_decay
    # no half-life configured → decay is a no-op
    legacy = Prefetcher(PrefetchConfig(), time_fn=clk)
    c = {1: 5}
    legacy.decay_demand("g", c)
    clk.t = 1e9
    legacy.decay_demand("g", c)
    assert c == {1: 5}
    with pytest.raises(ValueError, match="half_life_s"):
        PrefetchConfig(half_life_s=0.0)


def test_prefetch_demand_decay_ages_out_stale_hotness(graph):
    """Satellite: a vertex hot long ago must stop ranking hot — under a
    half-life, idle polls decay the demand counts before ranking, so stale
    traffic no longer earns prefetch compute."""
    clk = FakeClock()
    svc = PPRService(kappa=2, iterations=4, time_fn=clk,
                     prefetch=PrefetchConfig(top_n=4, k=5, max_per_pump=4,
                                             min_count=2, half_life_s=10.0))
    svc.register_graph("g", graph, formats=[26])
    for _ in range(2):                     # vertex 3 becomes hot (count 2)
        svc.submit(PPRQuery("g", 3, k=5, precision="auto")).result()
    svc.cache.invalidate(lambda k: True)
    assert svc.poll() == 1                 # idle poll at t=0: 3 is prefetched
    issued = svc.telemetry_summary()["prefetch_issued"]
    assert issued == 1
    # 20 half-lives later the old demand has fully cooled and been pruned
    clk.t = 200.0
    svc.cache.invalidate(lambda k: True)
    assert svc.poll() == 0                 # nothing hot → nothing issued
    assert svc.telemetry_summary()["prefetch_issued"] == issued
    assert svc.telemetry.query_vertex_counts["g"] == {}
    # fresh traffic re-heats under the decayed regime
    for _ in range(2):
        svc.submit(PPRQuery("g", 7, k=5, precision="auto")).result()
    svc.cache.invalidate(lambda k: True)
    assert svc.poll() == 1                 # recent hotness still prefetches
    assert svc.telemetry_summary()["prefetch_issued"] == issued + 1


# ---------------------------------------------------------------------------
# mesh-sharded delta path (subprocess with forced host devices, per run-book)
# ---------------------------------------------------------------------------
def _run(script: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_sharded_apply_delta_equivalence():
    """Acceptance: delta on a 4-shard mesh graph with non-divisible V — both
    the incremental-bucket path (no growth) and the full-repartition path
    (vertex growth changes the ceil-division layout) serve bit-identical to a
    fresh sharded registration AND to single-device serving."""
    print(_run("""
        import numpy as np, jax
        from repro.launch.mesh import make_mesh
        from repro.graphs import holme_kim_powerlaw
        from repro.graph_updates import random_delta
        from repro.ppr_serving import PPRQuery, PPRService

        g = holme_kim_powerlaw(203, m=4, seed=2)        # 203 % 4 != 0
        rng = np.random.default_rng(1)
        mesh = make_mesh((4,), ("shard",))

        for grow, label in ((0, "incremental-bucket"), (5, "full-repartition")):
            d = random_delta(g, rng, n_add=15, n_remove=6, grow=grow)
            svc = PPRService(kappa=4, iterations=8, cache_capacity=0)
            svc.register_graph("g", g, formats=[26], mesh=mesh)
            svc.serve([PPRQuery("g", 9, k=8, precision=26)])
            svc.apply_delta("g", d)
            merged, _ = d.apply(g)
            fresh = PPRService(kappa=4, iterations=8, cache_capacity=0)
            fresh.register_graph("g", merged, formats=[26], mesh=mesh)
            single = PPRService(kappa=4, iterations=8, cache_capacity=0)
            single.register_graph("g", merged, formats=[26])
            probe = [0, 9, 150, 202] + ([202 + grow] if grow else [])
            for v in probe:
                qs = [PPRQuery("g", v, k=8, precision=26)]
                a, b, c = (s.serve(qs)[0] for s in (svc, fresh, single))
                np.testing.assert_array_equal(a.vertices, b.vertices)
                np.testing.assert_array_equal(a.scores, b.scores)
                np.testing.assert_array_equal(a.scores, c.scores)
                qf = [PPRQuery("g", v, k=8)]
                af, bf = (s.serve(qf)[0] for s in (svc, fresh))
                np.testing.assert_array_equal(af.vertices, bf.vertices)
                np.testing.assert_array_equal(af.scores, bf.scores)
            print(label, "OK")
    """))
