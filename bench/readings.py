"""Readings that a cell's correctness limits are set from, in one process.

    python3 bench/readings.py --workload kron20.q25.backlog --seconds 20 \
        --seeds 1,2,3 [--control]

Without ``--control`` each seed is one run of the cell as ``run.py`` makes
it, and the numbers its check compared are printed.  With ``--control`` the
same is done for the control, the step down in precision that a later change
could be tempted to take: for a fixed-point cell the program at the next
narrower paper format (Q1.25 -> Q1.23), for a float32 cell the reference's
own recurrence computed in bfloat16 and put in the program's place for the
queries the window would send.  The control has to fail the check.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

NARROWER = {"Q1.25": "Q1.23", "Q1.23": "Q1.21", "Q1.21": "Q1.19"}


def bf16_control(workload: str, seed: int, seconds: float, root=ROOT,
                 graph=None) -> dict:
    """The float32 cell's check run on bfloat16 answers of the reference's
    recurrence, for as many of the window's queries as a run compares.
    ``graph`` overrides generator parameters, as in ``harness.set_up``."""
    import numpy as np

    from bench import check
    from bench.harness import linked_vertices
    from bench.load import Request
    from bench.manifest import Manifest
    from bench.reference import Reference, ppr_bf16
    from bench.traffic import make_schedule

    man = Manifest(root)
    cell = man.workload(workload)
    cfg = man.config(cell["config"])
    limits = man.limits(workload)
    svc = cfg["service"]
    gparams = {**cfg["graph"], **(graph or {})}
    n, src, dst = man.generator(gparams["generator"]).generate(gparams, seed)
    sched = make_schedule(man.traffic(cell["traffic"]), seed,
                          linked_vertices(n, src, dst), int(svc["kappa"]),
                          seconds)
    queries = sched.vertices[:int(limits["max_answers"])]
    reqs = []
    for i in range(0, len(queries), int(svc["kappa"])):
        block = queries[i:i + int(svc["kappa"])]
        P = ppr_bf16(n, src, dst, block, float(svc["alpha"]),
                     int(svc["iterations"]))
        for j, q in enumerate(block):
            col = P[:, j].astype(np.float64)
            col[q] = -np.inf
            top = np.lexsort((np.arange(n), -col))[:sched.k]
            reqs.append(Request(int(q), due=0.0, status=200, payload={
                "graph": cell["config"], "vertex": int(q), "k": sched.k,
                "precision": "f32",
                "recommendations": [{"vertex": int(v), "score": float(col[v])}
                                    for v in top]}))
    ref = Reference(n, src, dst, float(svc["alpha"]), int(svc["iterations"]))
    return check.compare(reqs, ref, graph=cell["config"], precision_key="f32",
                         k=sched.k, seed=seed,
                         max_answers=int(limits["max_answers"]))


def main() -> None:
    from bench.harness import run_cell
    from bench.manifest import Manifest

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()

    man = Manifest(ROOT)
    precision = man.traffic(man.workload(args.workload)["traffic"])["precision"]
    for seed in (int(s) for s in args.seeds.split(",")):
        if args.control and precision == "f32":
            numbers = bf16_control(args.workload, seed, args.seconds)
            label = "bf16 reference"
        else:
            label = NARROWER[precision] if args.control else precision
            result = run_cell(ROOT, args.workload, seed, args.seconds, False,
                              precision=label, log=lambda _m: None)
            numbers = {k: v["value"] for k, v in result["checks"].items()}
            numbers["correct"] = result["correct"]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "served_as": label, **numbers}), flush=True)


if __name__ == "__main__":
    main()
