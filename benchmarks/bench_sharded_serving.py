"""Sharded-serving benchmark: queries/s vs shard count.

The paper scales by partitioning the edge stream across memory channels; the
Top-K SpMV follow-up (arXiv 2103.04808) shows the same partitioning unlocks
multi-channel/multi-device bandwidth for the serving workload.  This measures
that end-to-end: one graph served by ``PPRService`` registered single-device
(shards=1) and on ``jax.sharding`` meshes of growing width, float32 and
fixed-point, reporting queries/s and wave latency per shard count.

    PYTHONPATH=src python benchmarks/bench_sharded_serving.py [--scale 0.02] [--dry-run]

Everything runs in the calling process, which must see the devices: on the
chip every shard count up to the visible device count runs; with
``JAX_PLATFORMS=cpu`` the entry points expose host devices
(``repro.launch.mesh.cpu_host_devices``) before JAX starts.

``--dry-run`` is the CI smoke path (tiny graph, shards 1/2, one precision).
Output is the house ``name,us_per_call,derived`` CSV.
"""
from __future__ import annotations

import argparse
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.graphs import holme_kim_powerlaw
from repro.launch.mesh import cpu_host_devices, make_mesh
from repro.ppr_serving import PPRQuery, PPRService


def run(scale: float = 0.02, n_queries: int = 32, kappa: int = 8,
        iterations: int = 10, shards: Sequence[int] = (1, 2, 4, 8),
        precisions: Sequence[Optional[int]] = (None, 26),
        seed: int = 0) -> List[Dict]:
    """One PPRService per (shards, precision), for every shard count the
    visible devices allow."""
    shards = [n for n in shards if n <= jax.device_count()]

    # deliberately not a multiple of any shard count: the ceil-division padded
    # layout is the production case, so it is the benchmarked one
    n_vertices = max(131, int(128000 * scale)) | 1
    g = holme_kim_powerlaw(n_vertices, m=3, seed=1)
    rng = np.random.default_rng(seed)
    users = rng.integers(0, g.num_vertices, n_queries)
    rows: List[Dict] = []
    for n_shards in shards:
        mesh = None if n_shards == 1 else make_mesh((n_shards,), ("shard",))
        for prec in precisions:
            svc = PPRService(kappa=kappa, iterations=iterations,
                             cache_capacity=0)       # measure compute, not cache
            svc.register_graph("g", g, formats=[p for p in (prec,) if p],
                               mesh=mesh)
            queries = [PPRQuery("g", int(v), k=10, precision=prec)
                       for v in users]
            svc.run_batch(queries[: min(kappa, n_queries)])  # warm up jit
            svc.telemetry.reset()      # count only the timed traffic
            svc.run_batch(queries)
            s = svc.telemetry_summary()
            engine_key = ("float" if prec is None else "fixed") if mesh is None \
                else ("sharded_float" if prec is None else "sharded_fixed")
            rows.append({
                "shards": n_shards,
                "precision": "f32" if prec is None else f"q{prec}",
                "engine": engine_key,
                "V": g.num_vertices,
                "E": g.num_edges,
                "kappa": kappa,
                "queries": n_queries,
                "queries_per_s": s["queries_per_s"],
                "p50_s": s["wave_latency_p50_s"],
                "p95_s": s["wave_latency_p95_s"],
                "engine_mean_s": s.get(f"engine_{engine_key}_latency_mean_s", 0.0),
                "engine_p95_s": s.get(f"engine_{engine_key}_latency_p95_s", 0.0),
                "waves": s["waves"],
            })
    return rows


def main(scale: float = 0.02, dry_run: bool = False) -> List[Dict]:
    if dry_run:
        rows = run(scale=0.005, n_queries=8, kappa=4, shards=(1, 2),
                   precisions=(26,))
    else:
        rows = run(scale=scale)
    print("# sharded_serving: name,us_per_call,derived")
    for r in rows:
        us = 1e6 / r["queries_per_s"] if r["queries_per_s"] else 0.0
        print(f"sharded_s{r['shards']}_{r['precision']},{us:.0f},"
              f"qps={r['queries_per_s']:.1f}"
              f";p50_us={r['p50_s']*1e6:.0f};p95_us={r['p95_s']*1e6:.0f}"
              f";V={r['V']};waves={r['waves']}"
              f";engine={r['engine']}"
              f";engine_p95_us={r['engine_p95_s']*1e6:.0f}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny graph, shards 1/2 — the CI smoke path")
    args = ap.parse_args()
    cpu_host_devices(8)
    main(scale=args.scale, dry_run=args.dry_run)
