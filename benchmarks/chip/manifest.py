"""Load ``BENCHMARK.json`` and the data files of one cell, and check the
manifest's shape: names, units, cross references and the share of
four-chip cells."""
from __future__ import annotations

import json
import pathlib
import re
from typing import List, Optional

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES_E2E = ("host_clock", "device_trace")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class Cell:
    """One entry of ``workloads`` with its configuration, traffic and
    workload files read, and the metrics it reports."""

    def __init__(self, manifest: dict, root: pathlib.Path, name: str):
        cells = {w["name"]: w for w in manifest["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(cells)})")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in manifest["configs"]}[
            self.entry["config"]]
        data = root / manifest["paths"][0]
        self.config = _read(root / cfg_entry["file"])
        self.traffic = _read(data / "traffic" / f"{self.entry['traffic']}.json")
        self.workload = _read(data / "workloads" / f"{name}.json")
        self.end_to_end = reported(manifest["end_to_end"], name)
        self.per_layer = [m for m in manifest["per_layer"]
                          if name in m.get("workloads", ())]


def _read(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load(root: pathlib.Path) -> dict:
    return _read(root / "BENCHMARK.json")


def reported(metrics: List[dict], cell: str) -> List[dict]:
    """The metrics of ``metrics`` that ``cell`` reports."""
    return [m for m in metrics
            if m.get("workloads") is None or cell in m["workloads"]]


def validate(manifest: dict, root: Optional[pathlib.Path] = None
             ) -> List[str]:
    """Problems with the manifest's shape; empty when it is sound."""
    errs = []
    configs = {c["name"]: c for c in manifest["configs"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    names = (list(configs) + list(cells) + list(e2e)
             + [m["name"] for m in manifest["per_layer"]]
             + [w["traffic"] for w in manifest["workloads"]]
             + [k for c in manifest["configs"] for k in c["reduced"]])
    errs += [f"bad name {n!r}" for n in names if not NAME.match(n)]
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    errs += [f"bad unit {m['unit']!r}" for m in metrics
             if not UNIT.match(m["unit"])]
    errs += [f"{m['name']}: better must be lower or higher" for m in metrics
             if m["better"] not in ("lower", "higher")]
    errs += [f"{m['name']}: end-to-end source {m['source']!r}"
             for m in manifest["end_to_end"] if m["source"] not in SOURCES_E2E]
    errs += [f"{m['name']}: source {m['source']!r}"
             for m in manifest["per_layer"] if m["source"] not in SOURCES]
    all_names = [m["name"] for m in metrics]
    errs += [f"duplicate metric {n}" for n in set(all_names)
             if all_names.count(n) > 1]
    if "setup_s" not in e2e:
        errs.append("no setup_s")
    for m in manifest["end_to_end"]:
        if not 0.01 <= m["bound"] <= 0.25:
            errs.append(f"{m['name']}: bound {m['bound']} outside [0.01, 0.25]")
    pairs = [(w["config"], w["traffic"]) for w in cells.values()]
    errs += [f"pair {p} twice" for p in set(pairs) if pairs.count(p) > 1]
    for w in cells.values():
        if w["config"] not in configs:
            errs.append(f"{w['name']}: no config {w['config']!r}")
        if w["chips"] not in (1, 4):
            errs.append(f"{w['name']}: chips {w['chips']}")
        rep = reported(manifest["end_to_end"], w["name"])
        if not [m for m in rep if m["name"] != "setup_s"]:
            errs.append(f"{w['name']}: reports no end-to-end metric "
                        "besides setup_s")
        if not [m for m in manifest["per_layer"]
                if w["name"] in m.get("workloads", ())]:
            errs.append(f"{w['name']}: reports no per-layer metric")
    for m in manifest["end_to_end"]:
        for c in m.get("workloads", ()):
            if c not in cells:
                errs.append(f"{m['name']}: no cell {c!r}")
    for m in manifest["per_layer"]:
        if "workloads" not in m:
            errs.append(f"{m['name']}: no workloads list")
        if m["moves"] not in e2e:
            errs.append(f"{m['name']}: moves unknown {m['moves']!r}")
            continue
        for c in m.get("workloads", ()):
            if c not in cells:
                errs.append(f"{m['name']}: no cell {c!r}")
            elif c not in [w for w in cells
                           if m["moves"] in {r["name"] for r in reported(
                               manifest["end_to_end"], w)}]:
                errs.append(f"{m['name']}: cell {c} does not report "
                            f"{m['moves']}")
    used = {w["config"] for w in cells.values()}
    errs += [f"config {c} used by no cell" for c in configs if c not in used]
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    if four > max(1, len(cells) // 2):
        errs.append(f"{four} four-chip cells of {len(cells)}")
    files = [c["file"] for c in configs.values()]
    errs += [f"config file {f} twice" for f in set(files)
             if files.count(f) > 1]
    if root is not None:
        for c in configs.values():
            if not c["file"].startswith(tuple(p + "/" for p in
                                              manifest["paths"])):
                errs.append(f"{c['name']}: file outside paths")
            if not (root / c["file"]).is_file():
                errs.append(f"{c['name']}: no file {c['file']}")
        data = root / manifest["paths"][0]
        for w in cells.values():
            for sub, n in (("workloads", w["name"]), ("traffic",
                                                      w["traffic"])):
                if not (data / sub / f"{n}.json").is_file():
                    errs.append(f"{w['name']}: no {sub}/{n}.json")
    return errs
