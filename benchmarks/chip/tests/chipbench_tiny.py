"""Tiny copies of the benchmark's cells for CPU tests: the same manifest,
drivers, metrics and references, at the sizes a test run holds that
``tiny/<config>.json`` gives each configuration."""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parents[1]
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

_spec = importlib.util.spec_from_file_location("chipbench_run",
                                               BENCH / "run.py")
run = importlib.util.module_from_spec(_spec)
sys.modules["chipbench_run"] = run
_spec.loader.exec_module(run)

CELL_OF_APP = {"md": "md216k_1chip", "vic": "vic256_1chip"}


def write_tiny(root: pathlib.Path, over: dict | None = None,
               src: pathlib.Path = ROOT) -> pathlib.Path:
    """A checkout-like tree under ``root``: the ``BENCHMARK.json`` of the
    tree ``src`` plus the data files of every cell, with each configuration
    cut to the sizes in its ``tests/tiny/<config>.json`` and ``over``
    (config key -> value) applied to every configuration. A configuration
    with no tiny file is copied as it is, so only a test that names its
    cell runs it."""
    man = json.loads((src / "BENCHMARK.json").read_text())
    bench = src / man["paths"][0]
    data = root / man["paths"][0]
    for sub in ("configs", "workloads", "traffic"):
        (data / sub).mkdir(parents=True, exist_ok=True)
    for c in man["configs"]:
        cfg = json.loads((src / c["file"]).read_text())
        tiny = bench / "tests" / "tiny" / f"{c['name']}.json"
        if tiny.is_file():
            cfg.update(json.loads(tiny.read_text()))
        cfg.update(over or {})
        (root / c["file"]).write_text(json.dumps(cfg))
    for w in man["workloads"]:
        for sub, name in (("workloads", w["name"]),
                          ("traffic", w["traffic"])):
            text = (bench / sub / f"{name}.json").read_text()
            (data / sub / f"{name}.json").write_text(text)
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


def run_tiny(root: pathlib.Path, cell: str, seed: int = 2 ** 40 + 3,
             seconds: float = 0.5, wrap=None) -> dict:
    """One run of ``cell`` on the CPU, as the benchmark's command runs it
    on the chip, minus the look for a TPU."""
    return run.run_cell(cell, seed, seconds, False, root=root,
                        require_tpu=False, wrap=wrap,
                        t_start=time.perf_counter(),
                        log=lambda *a, **k: None)
