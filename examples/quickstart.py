"""Quickstart — Lennard-Jones MD in ~30 lines (paper Listing 4.1).

    PYTHONPATH=src python examples/quickstart.py
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from repro.apps import md
from repro.core import runtime as RT
from repro.io import vtk


def main():
    # domain = unit cube, periodic; particles on a 10^3 lattice (Listing
    # 4.1). σ chosen so the lattice spacing (0.1) sits near the LJ minimum
    # (2^{1/6}σ) — the paper's 60^3/σ=0.1 setup relies on LAMMPS-style
    # capped equilibration to survive its deeply overlapping start.
    cfg = md.MDConfig(n_per_side=10, sigma=0.085, epsilon=1.0, dt=0.0005)
    ps, log = md.run(cfg, n_steps=200, thermal_v=0.3, log_every=40)
    for step, ekin, epot in log:
        print(f"step {step:4d}  E_kin {ekin:10.3f}  E_pot {epot:10.3f}  "
              f"E_tot {ekin + epot:10.3f}")
    out = pathlib.Path("artifacts/quickstart_md.vtk")
    out.parent.mkdir(parents=True, exist_ok=True)
    vtk.write_particles(out, ps.x, {"v": ps.props["v"]}, valid=ps.valid)
    print(f"wrote {out} (ParaView-loadable, paper §3.7)")


if __name__ == "__main__":
    RT.enable_compile_cache()
    main()
