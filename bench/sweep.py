"""Find the highest open-loop rate an open-loop cell's set-up sustains.

    python3 bench/sweep.py --workload pl2e5.q25.open --seed 7 \
        --rates 20,30,40,50 --seconds 15

Sets the cell up once, then offers its traffic at each rate for
``--seconds``, each window with its own seed vertices.  Before each window
the result cache is emptied and, where the traffic says so, filled with that
window's hot set, as a run's set-up fills it.  Per rate it prints
the answers per second completed, the latency median and 95th percentile,
and the median (``growth``) and 95th percentile (``tail_growth``) latency of
the last third of the requests over those of the first third: a rate is
sustained while the answers keep up with it and these stay near 1 (the
queue does not grow over the window).  Where the cache answers over half
the requests the median is a hit, and only ``tail_growth`` sees the queue.  The cell's traffic file then states
0.8 x the highest sustained rate.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> None:
    import numpy as np

    from bench.harness import answer_in_setup, serve, set_up
    from bench.traffic import make_schedule

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args()

    st = set_up(ROOT, args.workload, args.seed)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        params = {**st.params, "rate_qps": rate}
        sched = make_schedule(params, args.seed + 1 + i, st.linked,
                              st.kappa, args.seconds, st.cache_capacity)
        if i == 0:
            answer_in_setup(st, sched.warm, sched.k, "warm-up")
        st.svc.cache.invalidate(lambda _key: True)
        answer_in_setup(st, sched.prefill, sched.k, "prefill")
        out = serve(st, sched, args.seconds, False, None, time.perf_counter())
        reqs = sorted(out["requests"], key=lambda r: r.due)
        lat = np.asarray(out["latencies_s"]) * 1e3
        third = max(1, len(reqs) // 3)
        by_due = np.asarray([(r.recv or np.inf) - r.due for r in reqs]) * 1e3
        ok = [r for r in reqs if r.ok]
        span = max(r.recv for r in ok) - min(r.due for r in reqs)
        print(json.dumps({
            "rate_qps": rate, "sent": len(reqs), "answered": len(ok),
            "completed_per_s": len(ok) / span,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "growth": float(np.median(by_due[-third:])
                            / np.median(by_due[:third])),
            "tail_growth": float(np.percentile(by_due[-third:], 95)
                                 / np.percentile(by_due[:third], 95)),
            "compiles": out["compiles"]}), flush=True)


if __name__ == "__main__":
    main()
