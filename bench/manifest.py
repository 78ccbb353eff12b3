"""``BENCHMARK.json`` and the files it names, found by name.

Layout under the benchmark directory (``bench/``):

    configs/<config>.json        the deployment: graph generator and sizes,
                                 service and admission settings, and for a
                                 cell on more than one chip optionally the
                                 mesh (``service.mesh``: ``shape``, ``axes``,
                                 ``shard_axis``); the file the manifest's
                                 configuration entry names
    generators/<generator>.py    ``generate(params, seed) -> (V, src, dst)``;
                                 ``REHEARSAL``, the parameters a CPU
                                 rehearsal overrides to make a tiny graph;
                                 ``sizes(params) -> (V, arcs bound)`` and,
                                 where it runs on the device,
                                 ``device_program(params)`` (compile_check)
    traffic/<traffic>.json       parameters of the one traffic generator
    limits/<workload>.json       the correctness limits of one cell
    metrics/<metric>.py          ``read(ctx) -> float | None`` for one metric

A cell, configuration, traffic mix or metric is added by adding its files
and its entry; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import math
import re
from pathlib import Path
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES_E2E = {"host_clock", "device_trace"}
SETUP = "setup_s"
CHIPS = (1, 4)          # the machines a cell can ask for
MAX_CELLS = 24
MAX_TEXT = 200          # a why, a layer or a source: one line of this many


def _text_ok(text: str) -> bool:
    return 1 <= len(text) <= MAX_TEXT and not any(c in text for c in "\n\r\t")


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_file_" + re.sub(r"\W", "_", path.stem), path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Manifest:
    def __init__(self, root: Path):
        self.root = Path(root)
        self.dir = self.root / BENCH_DIR.name
        self.data = json.loads((self.root / "BENCHMARK.json").read_text())

    # ---- lookups by name --------------------------------------------------
    def _entry(self, kind: str, name: str) -> dict:
        for e in self.data[kind]:
            if e["name"] == name:
                return e
        raise KeyError(f"no {kind[:-1] if kind.endswith('s') else kind} "
                       f"{name!r} in BENCHMARK.json "
                       f"(have {[e['name'] for e in self.data[kind]]})")

    def workload(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        return json.loads((self.root / self._entry("configs", name)["file"])
                          .read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.dir / "traffic" / f"{name}.json").read_text())

    def limits(self, workload: str) -> dict:
        return json.loads((self.dir / "limits" / f"{workload}.json")
                          .read_text())

    def generator(self, name: str):
        return load_module(self.dir / "generators" / f"{name}.py")

    def reader(self, metric: str):
        return load_module(self.dir / "metrics" / f"{metric}.py")

    # ---- which metrics a cell reports -------------------------------------
    def end_to_end(self, workload: str) -> List[dict]:
        return [m for m in self.data["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer(self, workload: str) -> List[dict]:
        return [m for m in self.data["per_layer"]
                if workload in m.get("workloads", [])]

    # ---- the manifest's own rules -----------------------------------------
    def problems(self) -> List[str]:
        """What breaks the rules this benchmark keeps, as messages."""
        out: List[str] = []
        d = self.data
        for kind in ("configs", "workloads", "end_to_end", "per_layer"):
            names = [e["name"] for e in d[kind]]
            if len(set(names)) != len(names):
                out.append(f"repeated {kind} name")
            out += [f"bad name {n!r}" for n in names if not NAME.match(n)]
        metrics: Dict[str, dict] = {m["name"]: m
                                    for m in d["end_to_end"] + d["per_layer"]}
        if len(metrics) != len(d["end_to_end"]) + len(d["per_layer"]):
            out.append("an end-to-end and a per-layer metric share a name")
        for m in metrics.values():
            if not UNIT.match(m["unit"]):
                out.append(f"bad unit {m['unit']!r} of {m['name']}")
            if m["better"] not in ("lower", "higher"):
                out.append(f"{m['name']}: better must be lower or higher")
            if not (self.dir / "metrics" / f"{m['name']}.py").is_file():
                out.append(f"{m['name']}: no reader metrics/{m['name']}.py")
        for m in d["end_to_end"]:
            if m["source"] not in SOURCES_E2E:
                out.append(f"{m['name']}: end-to-end source {m['source']!r}")
        if SETUP not in {m["name"] for m in d["end_to_end"]}:
            out.append("no setup_s")
        if len(d["end_to_end"]) > 5:
            out.append("more than 4 end-to-end metrics besides setup_s")
        configs = {c["name"]: c for c in d["configs"]}
        meshes = {}
        for c in configs.values():
            out += [f"bad reduced key {k!r}" for k in c["reduced"]
                    if not NAME.match(k)]
            out += [f"config {c['name']}: {key} is not one line of 1 to "
                    f"{MAX_TEXT} characters" for key in ("source", "why")
                    if not _text_ok(c[key])]
            if not any(w["config"] == c["name"] for w in d["workloads"]):
                out.append(f"config {c['name']}: no cell uses it")
            if not (self.root / c["file"]).is_file():
                out.append(f"config {c['name']}: no file {c['file']}")
                continue
            cfg = self.config(c["name"])
            if not (cfg.get("assumed") and cfg.get("guarantees")):
                out.append(f"config {c['name']}: states no assumed sizes or "
                           f"no guarantees")
            if cfg["admission"]["kappa_max"] != cfg["service"]["kappa"]:
                out.append(f"config {c['name']}: admission kappa_max is not "
                           f"the service's kappa")
            mesh = cfg["service"].get("mesh")
            if mesh is not None:
                meshes[c["name"]] = mesh
                if len(mesh["shape"]) != len(mesh["axes"]) or \
                        mesh.get("shard_axis", mesh["axes"][0]) \
                        not in mesh["axes"]:
                    out.append(f"config {c['name']}: mesh shape, axes and "
                               f"shard_axis disagree")
        cells = d["workloads"]
        if len(cells) > MAX_CELLS:
            out.append(f"{len(cells)} cells, more than {MAX_CELLS}")
        four = sum(1 for w in cells if w["chips"] == 4)
        if four > max(1, len(cells) // 2):
            out.append(f"{four} of {len(cells)} cells on 4 chips, more than "
                       f"{max(1, len(cells) // 2)}")
        for w in cells:
            name = w["name"]
            if w["chips"] not in CHIPS:
                out.append(f"{name}: chips {w['chips']!r}, not one of {CHIPS}")
            if not _text_ok(w["why"]):
                out.append(f"{name}: why is not one line of 1 to {MAX_TEXT} "
                           f"characters")
            if w["config"] not in configs:
                out.append(f"{name}: unknown config {w['config']!r}")
            mesh = meshes.get(w["config"])
            if mesh is not None and math.prod(mesh["shape"]) != w["chips"]:
                out.append(f"{name}: the mesh of {w['config']} spans "
                           f"{math.prod(mesh['shape'])} chips, not "
                           f"{w['chips']}")
            for sub, fname in (("traffic", w["traffic"]), ("limits", name)):
                if not (self.dir / sub / f"{fname}.json").is_file():
                    out.append(f"{name}: no {sub}/{fname}.json")
            e2e = {m["name"] for m in self.end_to_end(name)}
            if SETUP not in e2e or len(e2e) < 2:
                out.append(f"{name}: reports no end-to-end metric besides "
                           f"setup_s")
            if not self.per_layer(name):
                out.append(f"{name}: reports no per-layer metric")
        for m in d["per_layer"]:
            if not _text_ok(m["layer"]):
                out.append(f"{m['name']}: layer is not one line of 1 to "
                           f"{MAX_TEXT} characters")
            if m["moves"] not in metrics or m["moves"] in \
                    {p["name"] for p in d["per_layer"]}:
                out.append(f"{m['name']}: moves {m['moves']!r}, which is no "
                           f"end-to-end metric")
                continue
            if not m.get("workloads"):
                out.append(f"{m['name']}: names no cell under workloads")
            for w in m.get("workloads", []):
                if w not in {c["name"] for c in d["workloads"]}:
                    out.append(f"{m['name']}: unknown cell {w}")
                elif m["moves"] not in {e["name"]
                                        for e in self.end_to_end(w)}:
                    out.append(f"{m['name']}: cell {w} does not report "
                               f"{m['moves']}")
        return out
