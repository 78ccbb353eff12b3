"""Every cell's pieces end to end on the CPU at a tiny size (its generator's
``REHEARSAL``): the graph, the service, the server, the load over sockets
and the check.  A rehearsal reports counts only: no clock, rate or device
number."""
import pytest

from bench.generators.holme_kim import REHEARSAL
from bench.harness import NoChip, run_cell
from bench.manifest import Manifest
from bench.tests.cells import CELLS, REPO, rehearse_cell


@pytest.mark.parametrize("workload", list(CELLS))
def test_rehearsal_is_correct_and_prints_no_device_number(root_of, workload):
    root = root_of(workload)
    r = rehearse_cell(root, workload, 2**31 + 11, 2.0)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["device"]["platform"] == "cpu"
    assert "memory_peak_bytes" not in r["device"] and "breakdown" not in r
    man = Manifest(root)
    for m in man.end_to_end(workload) + man.per_layer(workload):
        timed = m["source"] in ("host_clock", "device_trace")
        assert (m["name"] in r["metrics"]) != timed, m["name"]
    assert list(r)[-1] == "checks"


def test_zipf_rehearsal_counts_cache_hits(root_of):
    r = rehearse_cell(root_of("pl2e5.q25.zipf"), "pl2e5.q25.zipf", 5, 2.0)
    assert r["metrics"]["cache_hit_pct"]["value"] > 20


def test_the_real_run_refuses_the_cpu():
    with pytest.raises(NoChip):
        run_cell(REPO, "pl2e5.q25.open", 1, 1.0, False, graph=REHEARSAL,
                 log=lambda _m: None)
