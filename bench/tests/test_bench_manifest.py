"""BENCHMARK.json and the files it names."""
import json
import shutil
from pathlib import Path

import pytest

from bench.harness import rehearse
from bench.manifest import Manifest

REPO = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def man():
    return Manifest(REPO)


def test_manifest_keeps_its_rules(man):
    assert man.problems() == []


def test_manifest_holds_the_issues_names(man):
    d = man.data
    assert {m["name"] for m in d["end_to_end"]} == {
        "queries_per_s", "latency_p50_ms", "latency_p95_ms", "setup_s"}
    # cache_hit_pct waits for the zipf cell (PERF.md, Open questions)
    assert {m["name"] for m in d["per_layer"]} == {
        "iter_roofline_pct.backlog", "iter_roofline_pct.open",
        "topk_ms_per_wave.backlog", "device_idle_pct.backlog",
        "queue_wait_p50_ms"}
    assert {c["name"] for c in d["configs"]} == {"kron20", "pl2e5"}
    assert [w["name"] for w in d["workloads"]][0] == "kron20.q25.backlog"
    assert all(w["chips"] == 1 for w in d["workloads"])
    assert d["command"] == ["python3", "bench/run.py"]
    assert d["paths"] == ["bench"]


def test_every_config_lists_what_it_assumed(man):
    for c in man.data["configs"]:
        cfg = man.config(c["name"])
        assert cfg["assumed"] and cfg["guarantees"]
        assert cfg["admission"]["kappa_max"] == cfg["service"]["kappa"]


@pytest.mark.parametrize("workload", ["kron20.q25.backlog", "pl2e5.q25.open",
                                      "pl2e5.q25.zipf", "kron20.f32.backlog"])
def test_every_cell_resolves_and_reports(root_of, workload):
    man = Manifest(root_of(workload))
    assert man.problems() == []
    cell = man.workload(workload)
    assert man.config(cell["config"])["graph"]["generator"]
    assert man.traffic(cell["traffic"])["precision"]
    assert set(man.limits(workload)) >= {"max_answers", "unanswered",
                                         "malformed"}
    e2e = {m["name"] for m in man.end_to_end(workload)}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in man.per_layer(workload):
        assert m["moves"] in e2e
        assert callable(man.reader(m["name"]).read)


def test_bad_names_and_units_are_caught(tmp_path):
    d = json.loads((REPO / "BENCHMARK.json").read_text())
    d["end_to_end"][0]["unit"] = "queries per second"
    d["per_layer"][0]["name"] = "bad/name"
    d["per_layer"][1]["moves"] = "queue_wait_p50_ms"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(d))
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    problems = " | ".join(Manifest(tmp_path).problems())
    assert "bad unit" in problems and "bad name 'bad/name'" in problems
    assert "moves 'queue_wait_p50_ms'" in problems


def _files(root: Path):
    return {p.relative_to(root): p.read_bytes()
            for p in root.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


def test_a_cell_is_added_by_files_and_entries_alone(tmp_path):
    """A new configuration, traffic mix, cell and per-layer metric: new
    files under bench/ and new entries in BENCHMARK.json, no other edit."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = _files(tmp_path)
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "kron20.json").read_text())
    cfg.update(name="kron11", graph={**cfg["graph"], "scale": 11})
    (b / "configs" / "kron11.json").write_text(json.dumps(cfg))
    (b / "traffic" / "q21.backlog.json").write_text(json.dumps(
        {"loop": "closed", "clients": 8, "max_requests": 64,
         "vertices": "uniform", "precision": "Q1.21", "k": 5}))
    (b / "limits" / "kron11.q21.backlog.json").write_text(json.dumps(
        {"max_answers": 64, "unanswered": 0, "malformed": 0}))
    (b / "metrics" / "waves_per_query.py").write_text(
        "def read(ctx):\n"
        "    waves = sum(c.value for c in ctx.family('ppr_waves_total'))\n"
        "    return waves / len(ctx.requests) if ctx.requests else None\n")
    d = json.loads((tmp_path / "BENCHMARK.json").read_text())
    d["configs"].append({"name": "kron11", "source": "test",
                         "file": "bench/configs/kron11.json", "reduced": [],
                         "why": "throwaway"})
    d["workloads"].append({"name": "kron11.q21.backlog", "config": "kron11",
                           "traffic": "q21.backlog", "chips": 1,
                           "why": "throwaway"})
    d["end_to_end"][0]["workloads"].append("kron11.q21.backlog")
    d["per_layer"].append({"name": "waves_per_query", "unit": "waves/query",
                           "better": "lower", "source": "program_counter",
                           "layer": "scheduler", "moves": "queries_per_s",
                           "workloads": ["kron11.q21.backlog"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(d))

    after = _files(tmp_path)
    changed = {p for p in before if before[p] != after[p]}
    assert changed == {Path("BENCHMARK.json")}
    assert Manifest(tmp_path).problems() == []
    result = rehearse(tmp_path, "kron11.q21.backlog", 2**31 + 3, 2.0,
                      graph={"scale": 9})
    assert result["correct"] and result["failed"] == 0
    assert 0 < result["metrics"]["waves_per_query"]["value"] <= 1
    assert set(result["checks"]) == {"unanswered", "malformed"}
