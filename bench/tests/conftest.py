"""A copy of the benchmark with the cells planned for later PRs added
(``pl2e5.q25.zipf``, ``kron20.f32.backlog``: PERF.md, Open questions), so
that the tests exercise the float32 comparison and the result cache through
a whole rehearsal, as those cells will.  Their limits here are for tiny CPU
graphs only; a cell's own are read on the chip."""
import json
import shutil
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]

PLANNED = {
    "pl2e5.q25.zipf": ("pl2e5", "q25.zipf", {
        "max_answers": 256, "unanswered": 0, "malformed": 0,
        "mismatched": 0}, ["latency_p50_ms", "latency_p95_ms"],
        ["iter_roofline_pct.open", "queue_wait_p50_ms"]),
    "kron20.f32.backlog": ("kron20", "f32.backlog", {
        "max_answers": 16, "unanswered": 0, "malformed": 0,
        "err_max": 2e-05, "err_mean": 2e-07}, ["queries_per_s"],
        ["iter_roofline_pct.backlog", "topk_ms_per_wave.backlog",
         "device_idle_pct.backlog"]),
}


def add_planned_cells(root: Path) -> Path:
    """Copy the benchmark under ``root`` and add the planned cells."""
    shutil.copy(REPO / "BENCHMARK.json", root)
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    d = json.loads((root / "BENCHMARK.json").read_text())
    metrics = {m["name"]: m for m in d["end_to_end"] + d["per_layer"]}
    for name, (config, traffic, limits, e2e, layers) in PLANNED.items():
        d["workloads"].append({"name": name, "config": config,
                               "traffic": traffic, "chips": 1,
                               "why": "planned"})
        (root / "bench" / "limits" / f"{name}.json").write_text(
            json.dumps(limits))
        for m in e2e + layers:
            metrics[m]["workloads"].append(name)
    d["per_layer"].append({
        "name": "cache_hit_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "cache (ppr_serving/cache.py)",
        "moves": "latency_p50_ms", "workloads": ["pl2e5.q25.zipf"]})
    (root / "BENCHMARK.json").write_text(json.dumps(d))
    return root


@pytest.fixture(scope="session")
def planned_root(tmp_path_factory) -> Path:
    return add_planned_cells(tmp_path_factory.mktemp("planned"))


@pytest.fixture(scope="session")
def root_of(planned_root):
    """The benchmark root that holds a cell: the repo, or the copy with the
    planned cells."""
    return lambda workload: planned_root if workload in PLANNED else REPO
