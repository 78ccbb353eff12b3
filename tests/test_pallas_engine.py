"""Pallas-fused engine family: bit-exact parity against the composed
datapaths, delta re-packetization equivalence, early-exit driver parity,
and end-to-end serving through PPRService with engine="pallas".

Everything here runs the kernels under ``interpret=True`` (the default on
CPU-only hosts), so the suite is meaningful without a TPU."""
import numpy as np
import pytest

pytest.importorskip("jax.experimental.pallas")

import jax.numpy as jnp  # noqa: E402

from repro.autotune.convergence import ConvergencePolicy, run_until_converged  # noqa: E402
from repro.core.coo import COOGraph  # noqa: E402
from repro.core.fixed_point import format_for_bits  # noqa: E402
from repro.graph_updates.delta import EdgeDelta  # noqa: E402
from repro.kernels.fused_ppr import (  # noqa: E402
    build_fused_layout,
    fused_ppr_iteration,
)
from repro.ppr_serving import (  # noqa: E402
    PallasRegisteredGraph,
    PPRQuery,
    PPRService,
    get_engine,
)
from repro.ppr_serving.engine.pallas import fused_step_operands  # noqa: E402

ALPHA = 0.85
FMT = format_for_bits(20)
# prime V: the trailing vertex block is ragged, dangling tail included
V_PRIME = 641


def _graph(v=V_PRIME, e=2500, seed=0):
    rng = np.random.default_rng(seed)
    # sources capped below v-40 ⇒ the tail vertices are dangling
    return COOGraph.from_edges(rng.integers(0, v - 40, e),
                               rng.integers(0, v, e), v)


def _pallas_rg(g, **kw):
    kw.setdefault("packet", 64)
    kw.setdefault("v_tile", 128)     # multi-block on the prime-V test graphs
    return PallasRegisteredGraph("g", g, **kw)


def _drive(plan, pers, iterations):
    Vmat = plan.initial(jnp.asarray(pers, jnp.int32))
    P, iters = plan.iterate(lambda P_: plan.step(Vmat, P_), Vmat)
    return P, iters


def test_fixed_raw_uint32_parity_with_fixed_engine():
    g = _graph()
    pers = [5, 123, 640, 7]
    ref_rg = get_engine("float").make_graph("g", g)
    ref = get_engine("fixed").plan(ref_rg, FMT, alpha=ALPHA, iterations=8)
    pal = get_engine("pallas_fixed").plan(_pallas_rg(g), FMT, alpha=ALPHA,
                                          iterations=8)
    P_ref, _ = _drive(ref, pers, 8)
    P_pal, _ = _drive(pal, pers, 8)
    assert P_pal.dtype == jnp.uint32
    assert bool(jnp.array_equal(P_pal, P_ref))          # raw-bit equality


def test_float_parity_within_1e6():
    g = _graph(seed=3)
    pers = [1, 2, 3, 600]
    ref_rg = get_engine("float").make_graph("g", g)
    ref = get_engine("float").plan(ref_rg, alpha=ALPHA, iterations=8)
    pal = get_engine("pallas_float").plan(_pallas_rg(g), alpha=ALPHA,
                                          iterations=8)
    P_ref, _ = _drive(ref, pers, 8)
    P_pal, _ = _drive(pal, pers, 8)
    assert float(jnp.abs(P_pal - P_ref).max()) < 1e-6


def test_early_exit_parity_with_run_until_converged():
    # tiny absorbing graph: the fixed path hits a strict fixed point or a
    # period-2 cycle well inside the budget; the fused driver must return the
    # same state bit-for-bit AND the same iteration count
    g = _graph(v=97, e=300, seed=5)
    pers = [0, 9, 96]
    pol = ConvergencePolicy(min_iterations=2, check_every=1)
    budget = 80
    ref_rg = get_engine("float").make_graph("g", g)
    ref = get_engine("fixed").plan(ref_rg, FMT, alpha=ALPHA, iterations=budget)
    Vref = ref.initial(jnp.asarray(pers, jnp.int32))
    P_ref, iters_ref, _ = run_until_converged(
        lambda P_: ref.step(Vref, P_), Vref, budget, pol,
        fixed=True, scale=FMT.scale, track_deltas=False)
    pal = get_engine("pallas_fixed").plan(
        _pallas_rg(g, v_tile=64), FMT, alpha=ALPHA, iterations=budget,
        convergence=pol)
    P_pal, iters_pal = _drive(pal, pers, budget)
    assert iters_pal < budget                            # actually exited early
    assert iters_pal == iters_ref
    assert bool(jnp.array_equal(P_pal, P_ref))


def test_delta_repacketization_equals_fresh_registration():
    g = _graph(seed=7)
    rg = _pallas_rg(g)
    rg.fused_topology()
    rg.fused_values(FMT)
    rg.fused_values(None)
    delta = EdgeDelta(add_src=[3, 3, 500], add_dst=[640, 11, 2],
                      remove_src=[int(g.y[0]), int(g.y[5])],
                      remove_dst=[int(g.x[0]), int(g.x[5])])
    rg.apply_delta(delta)
    for eng_key in ("pallas_float", "pallas_fixed"):
        get_engine(eng_key).on_delta(rg, None)           # idempotent latch
    fresh = _pallas_rg(rg.source)
    lay, flay = rg.fused_layout(), fresh.fused_layout()
    for field in ("x2", "y2", "val2", "step_row", "step_dst", "step_src",
                  "step_first", "step_last"):
        assert np.array_equal(getattr(lay, field), getattr(flay, field)), field
    assert np.array_equal(np.asarray(rg.fused_values(FMT)),
                          np.asarray(fresh.fused_values(FMT)))
    assert np.array_equal(np.asarray(rg.fused_values(None)),
                          np.asarray(fresh.fused_values(None)))
    # and the incremental build only rebuilt the dirty dst blocks: clean
    # blocks must be the same host arrays, not equal copies
    dirty = set(np.unique(
        np.concatenate([[640, 11, 2], [int(g.x[0]), int(g.x[5])]])
        // rg.v_tile).tolist())
    kept = [d for d in range(lay.n_blk) if d not in dirty]
    assert kept, "test graph must leave at least one clean block"


def test_delta_vertex_growth_forces_full_rebuild():
    g = _graph(v=100, e=300, seed=11)
    rg = _pallas_rg(g, v_tile=64)
    rg.fused_values(FMT)
    assert rg.fused_layout().n_blk == 2
    rg.apply_delta(EdgeDelta(add_src=[1], add_dst=[199],
                             new_num_vertices=200))
    get_engine("pallas_fixed").on_delta(rg, None)
    lay = rg.fused_layout()
    assert lay.n_blk == 4 and lay.num_vertices == 200
    fresh = _pallas_rg(rg.source, v_tile=64)
    assert np.array_equal(lay.x2, fresh.fused_layout().x2)
    assert np.array_equal(np.asarray(rg.fused_values(FMT)),
                          np.asarray(fresh.fused_values(FMT)))


def test_service_end_to_end_bit_identical():
    g = _graph(seed=1)

    def serve(engine):
        svc = PPRService(kappa=4, iterations=6, cache_capacity=0)
        svc.register_graph("g", g, formats=[20], engine=engine)
        futs = [svc.submit(PPRQuery("g", v, k=5, precision=20))
                for v in (1, 7, 123, 640)]
        svc.flush()
        return [f.result() for f in futs]

    for ra, rb in zip(serve("single"), serve("pallas")):
        assert np.array_equal(ra.vertices, rb.vertices)
        assert np.array_equal(ra.scores, rb.scores)


def test_service_delta_then_serve_stays_bit_identical():
    g = _graph(seed=2)
    delta = EdgeDelta(add_src=[4, 9], add_dst=[77, 640])

    def serve(engine):
        svc = PPRService(kappa=2, iterations=5, cache_capacity=0)
        svc.register_graph("g", g, formats=[20], engine=engine)
        svc.apply_delta("g", delta)
        futs = [svc.submit(PPRQuery("g", v, k=5, precision=20))
                for v in (4, 640)]
        svc.flush()
        return [f.result() for f in futs]

    for ra, rb in zip(serve("single"), serve("pallas")):
        assert np.array_equal(ra.vertices, rb.vertices)
        assert np.array_equal(ra.scores, rb.scores)


def test_service_float_waves_serve_through_pallas():
    g = _graph(seed=4)
    svc = PPRService(kappa=4, iterations=6, cache_capacity=0)
    svc.register_graph("g", g, engine="pallas")
    f = svc.submit(PPRQuery("g", 3, k=5, precision=None))
    svc.flush()
    rec = f.result()
    assert rec.vertices.shape == (5,)
    assert np.all(np.isfinite(rec.scores))
    summ = svc.telemetry.summary()
    assert any("pallas_float" in str(k) for k in summ)


def test_pallas_family_rejects_mesh():
    svc = PPRService()
    with pytest.raises(ValueError):
        svc.register_graph("g", _graph(v=50, e=100), engine="pallas",
                           mesh=object())


def test_layout_covers_every_edge_once():
    g = _graph(seed=9)
    lay = build_fused_layout(g, 128, 64)
    real = sum(int((r != 0).sum()) for r in lay.row_val)
    # zero-valued real edges can't exist (stochastic normalization > 0)
    assert real == g.num_edges
    assert lay.step_row.shape == lay.step_dst.shape
    assert int(lay.step_first.sum()) == lay.n_blk  # one zero per dst block
    assert int(lay.step_last.sum()) == lay.n_blk   # one combine per dst block


def test_dst_chunks_cut_on_dst_blocks():
    from repro.kernels.coo_spmv import dst_chunks
    sd = np.array([0, 0, 0, 2, 2, 3, 5, 5, 5, 5])     # blocks 1 and 4 empty
    chunks = dst_chunks(sd, 7, max_steps=5)
    assert chunks == ((0, 5, 0, 3), (5, 10, 3, 7))
    for s0, s1, d0, d1 in chunks:
        assert s1 - s0 <= 5
        assert d0 <= sd[s0:s1].min() and sd[s0:s1].max() < d1
    assert dst_chunks(sd, 7) == ((0, 10, 0, 7),)
    with pytest.raises(ValueError):
        dst_chunks(sd, 7, max_steps=3)                 # block 5 needs 4 steps


@pytest.mark.parametrize("fixed", [True, False], ids=["q", "f32"])
def test_multi_launch_iteration_equals_one_launch(monkeypatch, fixed):
    """An iteration cut into several launches gives the same state and the
    same residual as one launch (raw-bit equal on the fixed path)."""
    import repro.kernels.coo_spmv as coo_spmv
    g = _graph(seed=6)
    pers = [2, 50, 640, 9]
    key, fmt = ("pallas_fixed", FMT) if fixed else ("pallas_float", None)

    def run():
        rg = _pallas_rg(g)
        plan = get_engine(key).plan(rg, fmt, alpha=ALPHA, iterations=3)
        P, _ = _drive(plan, pers, 3)
        Vmat = plan.initial(jnp.asarray(pers, jnp.int32))
        operands, statics = fused_step_operands(rg, fmt, ALPHA)
        res = fused_ppr_iteration(*operands, Vmat, P, **statics)[1]
        return P, res, len(rg.fused_layout().chunks)

    P1, r1, n1 = run()
    monkeypatch.setattr(coo_spmv, "MAX_LAUNCH_STEPS", 12)
    P2, r2, n2 = run()
    assert n1 == 1 and n2 > 2
    if fixed:
        assert bool(jnp.array_equal(P1, P2))
        assert bool(jnp.array_equal(r1[1], r2[1]))     # ∞-residual: a max
    else:
        assert float(jnp.abs(P1 - P2).max()) == 0.0
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2), rtol=1e-6)
