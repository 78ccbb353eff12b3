"""BENCHMARK.json and the files it names."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench.harness import rehearse
from bench.manifest import Manifest
from bench.tests.cells import CELLS, REPO, SRC

# what accepted benchmarks hold: none of it may leave silently
ACCEPTED = {
    "end_to_end": {"queries_per_s", "latency_p50_ms", "latency_p95_ms",
                   "latency_p75_ms", "setup_s"},
    "per_layer": {"iter_roofline_pct.backlog", "iter_roofline_pct.open",
                  "topk_ms_per_wave.backlog", "device_idle_pct.backlog",
                  "queue_wait_p50_ms", "queue_wait_p50_ms.zipf",
                  "cache_hit_pct", "http_self_ms_p50",
                  "wave_gap_ms_p50.open", "wave_gap_ms_p50.backlog"},
    "configs": {"kron20", "pl2e5"},
    "workloads": {"kron20.q25.backlog", "pl2e5.q25.open", "pl2e5.q25.zipf"},
}


@pytest.fixture(scope="module")
def man():
    return Manifest(REPO)


def test_manifest_keeps_its_rules(man):
    assert man.problems() == []


def test_manifest_holds_the_issues_names(man):
    d = man.data
    for kind, names in ACCEPTED.items():
        assert {e["name"] for e in d[kind]} >= names, kind
    assert d["workloads"][0]["name"] == "kron20.q25.backlog"
    assert d["command"] == ["python3", "bench/run.py"]
    assert d["paths"] == ["bench"]


def test_every_config_lists_what_it_assumed(man):
    for c in man.data["configs"]:
        cfg = man.config(c["name"])
        assert cfg["assumed"] and cfg["guarantees"]
        assert cfg["admission"]["kappa_max"] == cfg["service"]["kappa"]


@pytest.mark.parametrize("workload", list(CELLS))
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
    """A new configuration, traffic mix, per-layer metric, and cells on one
    chip and on four: new files under bench/ and new entries in
    BENCHMARK.json, no other edit.  The copy's own tests then take the new
    cells as they stand: the rules, the names, the resolve and the
    rehearsal tests, this one's on four host devices."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _files(tmp_path)
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "kron20.json").read_text())
    cfg.update(name="kron11", graph={**cfg["graph"], "scale": 11})
    (b / "configs" / "kron11.json").write_text(json.dumps(cfg))
    cfg.update(name="kron11.mesh4",
               service={**cfg["service"], "engine": "sharded",
                        "mesh": {"shape": [2, 2], "axes": ["data", "shard"],
                                 "shard_axis": "shard"}})
    (b / "configs" / "kron11.mesh4.json").write_text(json.dumps(cfg))
    (b / "traffic" / "q21.backlog.json").write_text(json.dumps(
        {"loop": "closed", "clients": 8, "max_requests": 64,
         "vertices": "uniform", "precision": "Q1.21", "k": 5}))
    (b / "limits" / "kron11.q21.backlog.json").write_text(json.dumps(
        {"max_answers": 64, "unanswered": 0, "malformed": 0}))
    (b / "limits" / "kron11.q21.mesh4.json").write_text(json.dumps(
        {"max_answers": 64, "unanswered": 0, "malformed": 0,
         "mismatched": 0}))
    (b / "metrics" / "waves_per_query.py").write_text(
        "def read(ctx):\n"
        "    waves = sum(c.value for c in ctx.family('ppr_waves_total'))\n"
        "    return waves / len(ctx.requests) if ctx.requests else None\n")
    d = json.loads((tmp_path / "BENCHMARK.json").read_text())
    new_cells = {"kron11.q21.backlog": ("kron11", 1),
                 "kron11.q21.mesh4": ("kron11.mesh4", 4)}
    for name, (config, chips) in new_cells.items():
        d["configs"].append({"name": config, "source": "test",
                             "file": f"bench/configs/{config}.json",
                             "reduced": [], "why": "throwaway"})
        d["workloads"].append({"name": name, "config": config,
                               "traffic": "q21.backlog", "chips": chips,
                               "why": "throwaway"})
        d["end_to_end"][0]["workloads"].append(name)
    d["per_layer"].append({"name": "waves_per_query", "unit": "waves/query",
                           "better": "lower", "source": "program_counter",
                           "layer": "scheduler", "moves": "queries_per_s",
                           "workloads": list(new_cells)})
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

    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(tmp_path), str(SRC)])}
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly", str(b / "tests" /
         "test_bench_manifest.py"), str(b / "tests" / "test_bench_rehearsal.py"),
         "-k", "kron11 or keeps_its_rules or holds_the_issues_names"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=900,
        check=False)
    assert done.returncode == 0, done.stdout[-4000:]
    # the rules, the names, and each new cell resolved and rehearsed
    passed = re.search(r"(\d+) passed", done.stdout)
    assert passed and int(passed.group(1)) >= 6, done.stdout[-4000:]


def _problems_with_cells(root: Path, chips) -> str:
    """The problems of a copy of the benchmark whose cells are clones of its
    first cell, one on each of ``chips``, joined into one string."""
    shutil.copy(REPO / "BENCHMARK.json", root)
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    d = json.loads((root / "BENCHMARK.json").read_text())
    first = d["workloads"][0]
    d["workloads"] = [{**first, "name": f"{first['name']}.{i}", "chips": c}
                      for i, c in enumerate(chips)]
    (root / "BENCHMARK.json").write_text(json.dumps(d))
    return " | ".join(Manifest(root).problems())


@pytest.mark.parametrize("chips,refused", [
    ([4] * 4 + [1] * 4, None),
    ([4] * 5 + [1] * 4, "5 of 9 cells on 4 chips, more than 4"),
    ([4] + [1], None),
    ([4] * 2 + [1], "2 of 3 cells on 4 chips, more than 1"),
    ([2] + [1] * 3, "chips 2, not one of (1, 4)"),
    ([1] * 24, None),
    ([1] * 25, "25 cells, more than 24"),
])
def test_manifest_refuses_four_chip_shares_and_counts_beyond_its_rules(
        tmp_path, chips, refused):
    problems = _problems_with_cells(tmp_path, chips)
    if refused is None:
        assert "chips" not in problems and "cells, more than" not in problems
    else:
        assert refused in problems


def test_manifest_refuses_a_config_without_its_statements(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    d = json.loads((tmp_path / "BENCHMARK.json").read_text())
    d["configs"][0]["source"] = "x" * 201
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(d))
    path = tmp_path / d["configs"][1]["file"]
    cfg = json.loads(path.read_text())
    del cfg["assumed"]
    cfg["admission"]["kappa_max"] = 2 * cfg["service"]["kappa"]
    path.write_text(json.dumps(cfg))
    problems = " | ".join(Manifest(tmp_path).problems())
    name0, name1 = d["configs"][0]["name"], d["configs"][1]["name"]
    assert f"config {name0}: source is not one line" in problems
    assert f"config {name1}: states no assumed sizes" in problems
    assert f"config {name1}: admission kappa_max" in problems


@pytest.mark.parametrize("mesh,chips,refused", [
    ({"shape": [2, 2], "axes": ["data", "shard"]}, 4, None),
    ({"shape": [4], "axes": ["shard"], "shard_axis": "shard"}, 4, None),
    ({"shape": [2, 2], "axes": ["data", "shard"]}, 1, "spans 4 chips, not 1"),
    ({"shape": [2], "axes": ["shard"]}, 4, "spans 2 chips, not 4"),
    ({"shape": [4], "axes": ["data", "shard"]}, 4, "disagree"),
    ({"shape": [4], "axes": ["shard"], "shard_axis": "edges"}, 4, "disagree"),
])
def test_manifest_refuses_a_mesh_that_does_not_fit_its_cell(
        tmp_path, mesh, chips, refused):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    d = json.loads((tmp_path / "BENCHMARK.json").read_text())
    first = d["workloads"][0]
    first["chips"] = chips
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(d))
    path = tmp_path / Manifest(tmp_path)._entry("configs", first["config"])[
        "file"]
    cfg = json.loads(path.read_text())
    cfg["service"]["mesh"] = mesh
    path.write_text(json.dumps(cfg))
    problems = " | ".join(Manifest(tmp_path).problems())
    if refused is None:
        assert "mesh" not in problems and "spans" not in problems, problems
    else:
        assert refused in problems
