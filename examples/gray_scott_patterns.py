"""Gray-Scott patterns (paper §4.3, Fig 6) — sweep Pearson classes.

    PYTHONPATH=src python examples/gray_scott_patterns.py
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from repro.apps import gray_scott as GS
from repro.core import runtime as RT
from repro.io import vtk


def main():
    outdir = pathlib.Path("artifacts/gray_scott")
    outdir.mkdir(parents=True, exist_ok=True)
    for name in ("alpha", "theta", "kappa"):
        F, k = GS.PEARSON[name]
        cfg = GS.GSConfig(shape=(64, 64), F=F, k=k, dt=1.0)
        u, v = GS.run(cfg, 3000)
        e = GS.pattern_energy(v)
        vtk.write_grid(outdir / f"pattern_{name}.vtk", v, name="v")
        print(f"Pearson {name:6s} (F={F}, k={k}): pattern energy {e:.4f}")


if __name__ == "__main__":
    RT.enable_compile_cache()
    main()
