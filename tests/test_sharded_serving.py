"""Multi-host sharded serving: sharded SpMV / PPR-step parity against the
single-device paths (bit-for-bit on the fixed path) and the end-to-end
PPRService mesh-vs-single-device equivalence.

Every num_vertices here is deliberately NOT divisible by the shard count —
the ceil-division padded layout (``sharded_vertex_layout``) is the regression
surface: ``make_sharded_spmv`` used to reject non-divisible V outright while
``partition_edges_by_dst`` already bucketed by ceil-division.

Subprocess with 8 forced host devices, so the main test process keeps its
single default device — per run-book (same pattern as test_distributed.py).
"""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_sharded_spmv_parity_nondivisible_vertices():
    """Float and fixed sharded SpMV vs spmv_float / spmv_fixed on V=500 over
    8 shards (ceil layout: v_local=63, 4 phantom rows on the last shard).
    The fixed path must be bit-for-bit; the float path numerically equal."""
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.core.fixed_point import Q1_25
        from repro.core.spmv import (make_sharded_spmv, make_sharded_spmv_fixed,
                                     partition_edges_by_dst, sharded_vertex_layout,
                                     spmv_fixed, spmv_float)
        from repro.graphs import erdos_renyi

        V, S = 500, 8
        v_local, v_pad = sharded_vertex_layout(V, S)
        assert v_local == 63 and v_pad == 504
        g = erdos_renyi(V, 4096, seed=0)
        mesh = make_mesh((S,), ("shard",))
        rng = np.random.default_rng(0)
        p = (rng.random((V, 4)) / V).astype(np.float32)

        # float path
        x, y, v = partition_edges_by_dst(g.x, g.y, g.val, V, S)
        f = make_sharded_spmv(mesh, "shard", V)
        with jax.set_mesh(mesh):
            out = f(jnp.asarray(x), jnp.asarray(y), jnp.asarray(v), jnp.asarray(p))
        ref = spmv_float(jnp.asarray(g.x), jnp.asarray(g.y), jnp.asarray(g.val),
                         jnp.asarray(p), V)
        assert out.shape == (V, 4), out.shape
        err = float(jnp.abs(out - ref).max())
        assert err < 1e-6, err

        # fixed path: bit-for-bit
        fmt = Q1_25
        vraw = g.quantized_val(fmt)
        xq, yq, vq = partition_edges_by_dst(g.x, g.y, vraw, V, S)
        assert vq.dtype == np.uint32, vq.dtype     # partitioner preserves dtype
        praw = fmt.from_float(jnp.asarray(p))
        ff = make_sharded_spmv_fixed(mesh, "shard", V, fmt)
        with jax.set_mesh(mesh):
            outq = ff(jnp.asarray(xq), jnp.asarray(yq), jnp.asarray(vq), praw)
        refq = spmv_fixed(jnp.asarray(g.x), jnp.asarray(g.y), jnp.asarray(vraw),
                          praw, V, fmt)
        assert outq.shape == (V, 4)
        assert bool(jnp.array_equal(outq, refq)), "fixed sharded SpMV not bit-exact"
        print("sharded spmv parity OK", err)
    """))


def test_sharded_ppr_steps_match_single_device():
    """10 driven iterations of the sharded step bodies vs the single-device
    step bodies: fixed bit-identical, float numerically equal.  V=389 (prime)
    over 8 shards."""
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.core.fixed_point import Q1_23
        from repro.core.ppr import (make_ppr_fixed_step, make_ppr_sharded_fixed_step,
                                    make_ppr_sharded_float_step,
                                    personalization_matrix,
                                    personalization_matrix_fixed, ppr_step_float)
        from repro.core.spmv import partition_edges_by_dst
        from repro.graphs import holme_kim_powerlaw

        V, S, alpha = 389, 8, 0.85
        g = holme_kim_powerlaw(V, m=4, seed=3)
        mesh = make_mesh((S,), ("shard",))
        pers = jnp.asarray([0, 17, 388], jnp.int32)
        dang = jnp.asarray(g.dangling)

        fmt = Q1_23
        vraw = g.quantized_val(fmt)
        xq, yq, vq = partition_edges_by_dst(g.x, g.y, vraw, V, S)
        Vm = personalization_matrix_fixed(V, pers, fmt)
        s_step = make_ppr_sharded_fixed_step(fmt, mesh, "shard", V, alpha)
        d_step = make_ppr_fixed_step(fmt, V, alpha)
        Ps = Pd = Vm
        for _ in range(10):
            Ps = s_step(jnp.asarray(xq), jnp.asarray(yq), jnp.asarray(vq),
                        dang, Vm, Ps)
            Pd = d_step(jnp.asarray(g.x), jnp.asarray(g.y), jnp.asarray(vraw),
                        dang, Vm, Pd)
        assert bool(jnp.array_equal(Ps, Pd)), "sharded fixed step not bit-exact"

        x, y, v = partition_edges_by_dst(g.x, g.y, g.val, V, S)
        Vmf = personalization_matrix(V, pers)
        sf_step = make_ppr_sharded_float_step(mesh, "shard", V, alpha)
        Pfs = Pfd = Vmf
        for _ in range(10):
            Pfs = sf_step(jnp.asarray(x), jnp.asarray(y), jnp.asarray(v),
                          dang, Vmf, Pfs)
            Pfd = ppr_step_float(jnp.asarray(g.x), jnp.asarray(g.y),
                                 jnp.asarray(g.val), dang, Vmf, Pfd,
                                 num_vertices=V, alpha=alpha)
        err = float(jnp.abs(Pfs - Pfd).max())
        assert err < 1e-7, err
        print("sharded ppr steps OK", err)
    """))


def test_service_mesh_vs_single_device_topk():
    """Acceptance: a graph registered on a 4-shard mesh with non-divisible
    num_vertices serves top-K bit-identical (fixed) / numerically equal
    (float) to single-device serving, with per-mesh wave telemetry."""
    print(_run("""
        import numpy as np, jax
        from repro.launch.mesh import make_mesh
        from repro.graphs import holme_kim_powerlaw
        from repro.ppr_serving import (PPRQuery, PPRService, RegisteredGraph,
                                       ShardedRegisteredGraph)

        g = holme_kim_powerlaw(601, m=5, seed=2)       # 601 % 4 != 0
        mesh = make_mesh((4,), ("shard",))
        verts = np.random.default_rng(0).integers(0, g.num_vertices, 8)

        def serve(mesh_arg):
            svc = PPRService(kappa=8, iterations=10)
            rg = svc.register_graph("g", g, formats=[26], mesh=mesh_arg)
            qs = [PPRQuery("g", int(v), k=10, precision=26) for v in verts] + \\
                 [PPRQuery("g", int(v), k=10) for v in verts]
            return svc, rg, svc.serve(qs)

        svc_m, rg_m, recs_m = serve(mesh)
        svc_s, rg_s, recs_s = serve(None)
        assert isinstance(rg_m, ShardedRegisteredGraph)
        assert type(rg_s) is RegisteredGraph
        assert rg_m.mesh_key == "mesh:shardx4"
        for i, (a, b) in enumerate(zip(recs_m, recs_s)):
            np.testing.assert_array_equal(a.vertices, b.vertices)
            if i < 8:   # fixed-point half: scores bit-identical through dequant
                np.testing.assert_array_equal(a.scores, b.scores)
            else:       # float half: numerically equal
                np.testing.assert_allclose(a.scores, b.scores, rtol=0, atol=1e-7)

        t = svc_m.telemetry_summary()
        assert t["waves_mesh:shardx4"] == 2, t
        assert t["queries_mesh:shardx4"] == 16, t
        ts = svc_s.telemetry_summary()
        assert ts["waves_single"] == 2 and ts["queries_single"] == 16, ts

        # repeat traffic on the meshed service hits the cache
        again = svc_m.serve([PPRQuery("g", int(verts[0]), k=10, precision=26)])
        assert again[0].source == "cache"
        print("mesh service e2e OK")
    """))


def test_fixed_engine_vs_sharded_fixed_engine_raw_uint32_equality():
    """Acceptance (engine layer): `FixedEngine` and `ShardedFixedEngine` plans
    driven over the same graph produce bit-identical raw uint32 state and
    identical top-K on non-divisible V — the backend seam did not perturb the
    datapath."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core.fixed_point import Q1_25
        from repro.graphs import holme_kim_powerlaw
        from repro.ppr_serving import PPRService, engine_for
        from repro.ppr_serving.graphs import (RegisteredGraph,
                                              ShardedRegisteredGraph)

        V = 389                                        # prime: no shard count divides it
        g = holme_kim_powerlaw(V, m=4, seed=3)
        mesh = make_mesh((8,), ("shard",))
        rg_single = RegisteredGraph("g", g)
        rg_sharded = ShardedRegisteredGraph("g", g, mesh)
        fixed = engine_for("single", True)
        sharded = engine_for("sharded", True)
        assert fixed.key == "fixed" and sharded.key == "sharded_fixed"

        plans = [eng.plan(rg, Q1_25, alpha=0.85, iterations=10)
                 for eng, rg in ((fixed, rg_single), (sharded, rg_sharded))]
        pers = jnp.asarray([0, 17, 200, 388], jnp.int32)
        states = []
        for plan in plans:
            assert plan.fixed and plan.scale == Q1_25.scale
            Vmat = plan.initial(pers)
            P, iters = plan.iterate(lambda P_: plan.step(Vmat, P_), Vmat)
            assert iters == 10
            states.append(np.asarray(P))
        assert states[0].dtype == states[1].dtype == np.uint32
        np.testing.assert_array_equal(states[0], states[1])   # raw bit equality

        tops = [plan.topk(jnp.asarray(s), 10, pers)
                for plan, s in zip(plans, states)]
        np.testing.assert_array_equal(np.asarray(tops[0][0]),
                                      np.asarray(tops[1][0]))
        np.testing.assert_array_equal(np.asarray(tops[0][1]),
                                      np.asarray(tops[1][1]))
        print("engine raw parity OK")
    """))


def test_sharded_graph_pre_quantizes_shards_and_purges_on_reregister():
    """register_graph(formats=[...], mesh=...) pre-partitions quantized shard
    values; re-registration drops the meshed graph's pending queries (3-part
    wave keys must keep the name-prefix purge working)."""
    print(_run("""
        import jax
        from repro.launch.mesh import make_mesh
        from repro.core.fixed_point import Q1_25
        from repro.graphs import erdos_renyi
        from repro.ppr_serving import PPRQuery, PPRService

        g = erdos_renyi(203, 1500, seed=1)             # 203 % 4 != 0
        mesh = make_mesh((4,), ("shard",))
        svc = PPRService(kappa=8, iterations=5)
        rg = svc.register_graph("g", g, formats=[26], mesh=mesh)
        assert Q1_25 in rg._sharded_quantized          # pre-partitioned at registration

        assert not svc.submit(PPRQuery("g", 3, k=5, precision=26)).done()
        assert svc.scheduler.pending() == 1
        svc.register_graph("g", g, formats=[26], mesh=mesh)
        assert svc.scheduler.pending() == 0            # purge saw the 3-part key
        print("sharded registration OK")
    """))
