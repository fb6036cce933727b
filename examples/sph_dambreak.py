"""SPH dam break (paper §4.2) with VTK frames + checkpoint/restart.

    PYTHONPATH=src python examples/sph_dambreak.py [--steps 400]
"""
import argparse
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from repro.apps import sph
from repro.core import runtime as RT
from repro.io import checkpoint as CK, vtk


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--frame-every", type=int, default=100)
    args = ap.parse_args()

    cfg = sph.SPHConfig(dp=0.03, box=(1.6, 0.8), fluid=(0.4, 0.4))
    ps = sph.init_dam_break(cfg)
    print(f"{int(ps.count())} particles "
          f"(h={cfg.h:.4f}, c_s={cfg.c_sound:.1f} m/s)")
    outdir = pathlib.Path("artifacts/sph")
    outdir.mkdir(parents=True, exist_ok=True)
    t = 0.0
    for i in range(args.steps):
        ps, dt, ovf = sph.sph_step(ps, cfg, euler=(i % cfg.verlet_reset == 0))
        t += float(dt)
        assert int(ovf) == 0
        if (i + 1) % args.frame_every == 0:
            vtk.write_particles(outdir / f"frame_{i + 1:05d}.vtk", ps.x,
                                {"rho": ps.props["rho"], "v": ps.props["v"]},
                                valid=ps.valid)
            print(f"step {i + 1}: t={t:.3f}s -> frame written")
    CK.save_particles(outdir / "checkpoint", ps, step=args.steps,
                      meta={"t": t})
    print(f"checkpoint at t={t:.3f}s -> {outdir}/checkpoint "
          f"(elastic: reloadable on any device count)")


if __name__ == "__main__":
    RT.enable_compile_cache()
    main()
