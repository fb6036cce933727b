"""Tiny copies of the benchmark's cells for CPU tests: the same manifest,
drivers, metrics and references, at sizes a test run holds."""
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

TINY = {
    "md_lj_216k": {"n_per_side": 12, "box": 1.2},
    "md_lj_857k_slab4": {"n_per_side": 20, "box": 2.0},
    "vic_ring_256": {"shape": [32, 16, 16]},
}
CELL_OF_APP = {"md": "md216k_1chip", "vic": "vic256_1chip"}


def write_tiny(root: pathlib.Path, over: dict | None = None) -> pathlib.Path:
    """A checkout-like tree under ``root``: BENCHMARK.json plus the data
    files of every cell, with each configuration cut to its TINY size and
    ``over`` (config key -> value) applied to every configuration."""
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    data = root / man["paths"][0]
    for sub in ("configs", "workloads", "traffic"):
        (data / sub).mkdir(parents=True, exist_ok=True)
    for c in man["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg.update(TINY[c["name"]], **(over or {}))
        (root / c["file"]).write_text(json.dumps(cfg))
    for w in man["workloads"]:
        for sub, name in (("workloads", w["name"]),
                          ("traffic", w["traffic"])):
            src = BENCH / sub / f"{name}.json"
            (data / sub / f"{name}.json").write_text(src.read_text())
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
