"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--scale 0.02] [--full] [--json DIR]

Prints ``name,us_per_call,derived`` CSV per row.  --full uses the paper's
graph sizes (|V| = 1e5/2e5, |E| ≈ 1e6/2e6 — minutes on CPU); default scale
runs in ~2 minutes.

``--json DIR`` additionally writes one machine-readable ``BENCH_<section>.json``
per section ({"bench", "scale", "rows": [...]}) so the perf trajectory can be
tracked across commits without re-parsing the human CSV.

Every section runs in this one process (the chip belongs to one process at a
time).  With ``JAX_PLATFORMS=cpu`` the harness exposes 8 host devices for the
sharded section; the persistent compile cache is placed by
``repro.launch.compile_cache.use_compile_cache``.

``--check`` (with ``--json``) verifies the baselines after the sweep: every
section that ran must have written a parseable, non-empty file, and a section
that was *skipped* must not leave a baseline behind — a silently-skipped
section would otherwise keep a stale committed baseline looking current.
Exits non-zero on any violation (the CI gate in scripts/ci.sh).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any

import numpy as np

from benchmarks import (bench_accuracy, bench_autotune, bench_convergence,
                        bench_graph_updates, bench_ppr, bench_serving_http,
                        bench_serving_ppr, bench_sharded_serving, bench_spmv)
from benchmarks import roofline_report
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import cpu_host_devices


def _jsonable(o: Any):
    """JSON encoder default for the numpy scalars/arrays bench rows carry."""
    if isinstance(o, np.integer):
        return int(o)
    if isinstance(o, np.floating):
        return float(o)
    if isinstance(o, np.ndarray):
        return o.tolist()
    return str(o)


def _dump(json_dir: str, section: str, scale: float, rows) -> None:
    path = os.path.join(json_dir, f"BENCH_{section}.json")
    with open(path, "w") as f:
        json.dump({"bench": section, "scale": scale, "rows": rows or []},
                  f, indent=1, default=_jsonable)
    print(f"[json] wrote {path}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--full", action="store_true", help="paper-size graphs")
    ap.add_argument("--dry-run", action="store_true",
                    help="CI smoke: tiny graphs, reduced configs, every section "
                         "— with --json this produces the BENCH_<section>.json "
                         "baselines the perf trajectory is tracked against")
    ap.add_argument("--json", metavar="DIR", nargs="?", const=".", default=None,
                    help="also write BENCH_<section>.json rows into DIR")
    ap.add_argument("--check", action="store_true",
                    help="after the sweep, fail unless every ran section wrote "
                         "a parseable non-empty BENCH_<section>.json and no "
                         "skipped section left a stale baseline (needs --json)")
    args = ap.parse_args()
    if args.check and not args.json:
        ap.error("--check requires --json (it verifies the written baselines)")
    cpu_host_devices(8)
    use_compile_cache()
    scale = 1.0 if args.full else args.scale
    if args.dry_run:
        # sections without a native dry-run mode shrink through scale alone
        scale = min(scale, 0.005)
    dry = args.dry_run
    if args.json:
        os.makedirs(args.json, exist_ok=True)

    sections = [
        ("ppr", "bench_ppr (paper Fig. 3: speedup vs bit-width x 8 graphs)",
         lambda: bench_ppr.main(scale=scale)),
        ("accuracy", "bench_accuracy (paper Figs. 4/5/6: accuracy vs bit-width)",
         lambda: bench_accuracy.main(scale=scale)),
        ("convergence", "bench_convergence (paper Fig. 7: fixed vs float convergence)",
         lambda: bench_convergence.main(scale=scale)),
        ("spmv", "bench_spmv (paper Table 2 analogue: kernel characterization)",
         lambda: bench_spmv.main(scale=scale)),
        ("serving_ppr", "bench_serving_ppr (PPRService: queries/s, p50/p95 vs kappa x precision)",
         lambda: bench_serving_ppr.main(scale=scale, dry_run=dry)),
        ("autotune", "bench_autotune (adaptive precision: quality targets vs static formats)",
         lambda: bench_autotune.main(scale=scale, dry_run=dry)),
        ("sharded_serving", "bench_sharded_serving (mesh serving: queries/s vs shard count)",
         lambda: bench_sharded_serving.main(scale=scale, dry_run=dry)),
        ("graph_updates", "bench_graph_updates (delta apply latency, warm vs cold iterations, scoped invalidation)",
         lambda: bench_graph_updates.main(scale=scale, dry_run=dry)),
        ("serving_http", "bench_serving_http (HTTP tier: latency under load, shed/degrade/recover)",
         lambda: bench_serving_http.main(scale=scale, dry_run=dry)),
        ("roofline", "roofline (dry-run artifacts; EXPERIMENTS.md section Roofline)",
         lambda: roofline_report.main()),
    ]
    ran, no_baseline = [], []
    for i, (section, title, fn) in enumerate(sections):
        print(("\n" if i else "") + f"## {title}")
        try:
            rows = fn()
        except FileNotFoundError as e:
            # roofline reads pre-generated experiments/roofline artifacts;
            # their absence must not sink the rest of a --json run
            print(f"[skip] {section}: {e}")
            no_baseline.append(section)
            continue
        if rows is None:
            # report-only section (prints, returns no row schema): it has no
            # baseline to write or verify
            no_baseline.append(section)
            continue
        ran.append(section)
        if args.json:
            _dump(args.json, section, scale, rows)
    if args.check:
        _check_baselines(args.json, ran, no_baseline)


def _check_baselines(json_dir: str, ran, no_baseline) -> None:
    """CI gate: the sweep's baselines must be fresh, parseable, non-empty —
    and a section that produced no rows this sweep (skipped, or report-only)
    must not leave a stale baseline committed."""
    problems = []
    for section in ran:
        path = os.path.join(json_dir, f"BENCH_{section}.json")
        if not os.path.exists(path):
            problems.append(f"{section}: ran but wrote no baseline ({path})")
            continue
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            problems.append(f"{section}: baseline unreadable ({e})")
            continue
        if not doc.get("rows"):
            problems.append(f"{section}: baseline has no rows ({path})")
    for section in no_baseline:
        path = os.path.join(json_dir, f"BENCH_{section}.json")
        if os.path.exists(path):
            problems.append(
                f"{section}: produced no rows this sweep but a baseline "
                f"exists — stale, delete {path} or unbreak the section")
    if problems:
        print("[check] FAILED:")
        for p in problems:
            print(f"  - {p}")
        sys.exit(1)
    print(f"[check] {len(ran)} baselines OK"
          + (f" ({len(no_baseline)} sections without baselines)"
             if no_baseline else ""))


if __name__ == "__main__":
    main()
