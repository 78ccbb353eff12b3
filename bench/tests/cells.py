"""The cells the tests run, read from the files at collection time: every
cell of ``BENCHMARK.json`` and every planned one of ``planned.json`` that it
does not hold yet.  A cell, configuration or per-layer metric added to
either file is tested with no test file edited.

``add_planned_cells`` writes a copy of the benchmark that holds the planned
cells too; ``rehearse_cell`` rehearses any cell, one on more than one chip in
a child process with as many CPU host devices."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path
from typing import Dict, Optional

import repro

from bench.harness import rehearse
from bench.manifest import Manifest

REPO = Path(__file__).resolve().parents[2]
PLANNED = json.loads((Path(__file__).with_name("planned.json")).read_text())
SRC = Path(next(iter(repro.__path__))).resolve().parent
CHILD_TIMEOUT_S = 600

# the child rehearses one multi-chip cell and prints the result as JSON
_CHILD = """
import json, sys
from pathlib import Path
from repro.launch.mesh import cpu_host_devices
cpu_host_devices(int(sys.argv[2]))
from bench.harness import rehearse
root, workload, seed, seconds, graph, precision = json.loads(sys.argv[1])
print(json.dumps(rehearse(Path(root), workload, seed, seconds, graph,
                          precision)))
"""


def _config_with(base: dict, overrides: dict) -> dict:
    """``base`` with each key of ``overrides`` set; a dict merges into the
    dict it replaces."""
    out = dict(base)
    for key, value in overrides.items():
        out[key] = {**out[key], **value} if isinstance(value, dict) else value
    return out


def _table(root: Path) -> Dict[str, dict]:
    """name -> the cell's entry with its configuration file's ``service``
    and its traffic's ``precision``, for every real and planned cell."""
    man = Manifest(root)
    services = {c["name"]: man.config(c["name"])["service"]
                for c in man.data["configs"]}
    for c in PLANNED["configs"]:
        services.setdefault(c["name"], _config_with(
            services[c["from"]], c["set"].get("service", {})))
    real = {w["name"] for w in man.data["workloads"]}
    cells = man.data["workloads"] + [w for w in PLANNED["workloads"]
                                     if w["name"] not in real]
    return {w["name"]: {**w, "service": services[w["config"]],
                        "precision": man.traffic(w["traffic"])["precision"],
                        "planned": w["name"] not in real}
            for w in cells}


CELLS = _table(REPO)


def cells_where(**want):
    """Names of the cells whose entry holds each ``key=value`` of ``want``
    (``engine`` is looked up in the configuration's service)."""
    def holds(c, key, value):
        return (c["service"]["engine"] if key == "engine" else c[key]) == value
    return [n for n, c in CELLS.items()
            if all(holds(c, k, v) for k, v in want.items())]


def add_planned_cells(root: Path) -> Path:
    """Copy the benchmark under ``root`` and add the planned cells, with
    their configurations, limits and per-layer entries, that it lacks."""
    shutil.copy(REPO / "BENCHMARK.json", root)
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    d = json.loads((root / "BENCHMARK.json").read_text())
    have = {kind: {e["name"] for e in d[kind]}
            for kind in ("configs", "workloads", "per_layer")}
    entries = {c["name"]: c for c in d["configs"]}
    for c in PLANNED["configs"]:
        if c["name"] in have["configs"]:
            continue
        base = entries[c["from"]]
        cfg = _config_with(json.loads((root / base["file"]).read_text()),
                           c["set"])
        file = f"bench/configs/{c['name']}.json"
        (root / file).write_text(json.dumps({**cfg, "name": c["name"]}))
        d["configs"].append({**base, "name": c["name"], "file": file,
                             "why": c["why"]})
    d["per_layer"] += [m for m in PLANNED["per_layer"]
                       if m["name"] not in have["per_layer"]]
    metrics = {m["name"]: m for m in d["end_to_end"] + d["per_layer"]}
    for w in PLANNED["workloads"]:
        if w["name"] in have["workloads"]:
            continue
        d["workloads"].append({**{k: w[k] for k in ("name", "config",
                                                    "traffic", "chips")},
                               "why": "planned"})
        (root / "bench" / "limits" / f"{w['name']}.json").write_text(
            json.dumps(w["limits"]))
        for m in w["metrics"]:
            metrics[m]["workloads"].append(w["name"])
    (root / "BENCHMARK.json").write_text(json.dumps(d))
    return root


def rehearse_cell(root: Path, workload: str, seed: int, seconds: float,
                  graph: Optional[Dict] = None, precision=None) -> Dict:
    """``harness.rehearse`` of one cell; a cell on more than one chip runs
    in a child process that has that many CPU host devices."""
    chips = int(Manifest(root).workload(workload)["chips"])
    if chips == 1:
        return rehearse(root, workload, seed, seconds, graph, precision)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(REPO), str(SRC)]),
           "XLA_FLAGS": re.sub(r"--xla_force_host_platform_device_count=\S*",
                               "", os.environ.get("XLA_FLAGS", ""))}
    args = json.dumps([str(root), workload, seed, seconds, graph, precision])
    done = subprocess.run([sys.executable, "-c", _CHILD, args, str(chips)],
                          env=env, capture_output=True, text=True,
                          timeout=CHILD_TIMEOUT_S, check=False)
    if done.returncode != 0:
        raise RuntimeError(f"rehearsal of {workload} on {chips} host devices "
                           f"exited {done.returncode}:\n{done.stderr[-4000:]}")
    return json.loads(done.stdout.splitlines()[-1])
