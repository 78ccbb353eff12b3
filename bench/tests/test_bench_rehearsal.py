"""Every cell's pieces end to end on the CPU at a tiny size: the graph, the
service, the server, the load over sockets and the check.  A rehearsal
reports counts only: no clock, rate or device number."""
from pathlib import Path

import pytest

from bench.harness import NoChip, rehearse, run_cell

REPO = Path(__file__).resolve().parents[2]
TINY = {"kron20": {"scale": 9}, "pl2e5": {"num_vertices": 3000}}
CELLS = ["kron20.q25.backlog", "pl2e5.q25.open", "pl2e5.q25.zipf",
         "kron20.f32.backlog"]


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_is_correct_and_prints_no_device_number(root_of, workload):
    config = workload.split(".")[0]
    r = rehearse(root_of(workload), workload, 2**31 + 11, 2.0, TINY[config])
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["device"]["platform"] == "cpu"
    assert "memory_peak_bytes" not in r["device"] and "breakdown" not in r
    for name in ("queries_per_s", "latency_p50_ms", "latency_p95_ms",
                 "setup_s", "iter_roofline_pct.open", "device_idle_pct.backlog"):
        assert name not in r["metrics"]
    assert list(r)[-1] == "checks"


def test_zipf_rehearsal_counts_cache_hits(planned_root):
    r = rehearse(planned_root, "pl2e5.q25.zipf", 5, 2.0, TINY["pl2e5"])
    assert r["metrics"]["cache_hit_pct"]["value"] > 20


def test_the_real_run_refuses_the_cpu():
    with pytest.raises(NoChip):
        run_cell(REPO, "pl2e5.q25.open", 1, 1.0, False,
                 graph=TINY["pl2e5"], log=lambda _m: None)
