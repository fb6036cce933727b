"""Benchmark harness — one module per paper table/figure (DESIGN.md §6).
Prints ``name,us_per_call,derived`` CSV.

  bench_md       — paper Table 2 (LJ MD strong scaling reference)
  bench_sph      — paper Table 3 (SPH time fractions)
  bench_stencil  — paper Table 4 / Fig 7 (Gray-Scott)
  bench_vortex   — paper Fig 9 (vortex-in-cell, Poisson split) + the
                    vic_dist8_sharded_mesh row: sharded DistributedField
                    step (slab FFT + halo-reduce P2M) vs the frozen PR-4
                    replicated-psum baseline on 8 forced host devices
  bench_interp   — paper §4.4 M'4 P2M/M2P + remesh (m4_interp vs oracle)
  bench_dem      — paper Fig 11 (DEM avalanche): per-step rebuild + the
                    skin-amortized cached-contact-list row
  bench_cmaes    — paper Fig 12 (PS-CMA-ES)
  backend_compare — unified cell-pair engine: jnp vs pallas(interpret)
                    timing + relative divergence for MD / SPH / DEM
  bench_distributed — MD weak scaling on 1/2/4/8 forced host devices
                    (workloads shared with tests/distributed); rows carry
                    the shared-CPU caveat and are mirrored with it into
                    artifacts/bench_distributed.json
  bench_sim_engine — unified make_sim_step engine vs frozen pre-refactor
                    steps (MD+SPH, serial + 8-device): no step-time
                    regression (ratio gate 1.05)
  bench_fleet    — batched ensemble step vs python-loop of single runs
                    (sims/sec; speedup gate 2.0 at batch 32) + the batch
                    axis sharded over 8 forced host devices; rows mirror
                    into artifacts/bench_fleet.json under the
                    repro-fleet-metrics/v1 schema
  bench_overlap  — split-phase interior/boundary stepping gate: the
                    overlapped make_sim_step schedules the ghost_get
                    ppermute before the interior pair fusions (HLO order
                    check via launch/hlo_analysis.overlap_report) and is
                    no slower than the blocking chain on 8 forced host
                    devices; rows mirror into artifacts/bench_overlap.json
  bench_pencil   — 2-D pencil FFT Poisson vs the slab path: the pencil's
                    widest transpose moves <= 6/7 of the slab's per-device
                    wire bytes (HLO all-to-all replica-group count via
                    launch/hlo_analysis.all_to_all_report; total bytes
                    honestly higher, logged) + equivalence + wall gates;
                    rows mirror into artifacts/bench_pencil.json
  bench_reuse    — skin-amortized ghost-reuse gates (MD + SPH, 8 forced
                    host devices): update steps ship <= 0.5x a rebuild
                    step's ppermute wire bytes (HLO conditional split via
                    launch/hlo_analysis.collective_permute_report),
                    trajectory equivalence <= 1e-5 with clean flags, and
                    the amortized loop <= 0.85x the every-step engine;
                    rows mirror into artifacts/bench_reuse.json

Usage: python benchmarks/run.py [--all] [--only NAME[,NAME...]]
  --all  (default) run every module; a module that raises is reported as
         a `<name>_error` row and the harness keeps going — a fresh clone
         with no artifacts must still complete the sweep.
  --only run the named module(s) only (e.g. --only bench_overlap).
"""
import argparse
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

MODULES = (
    "bench_md", "bench_sph", "bench_stencil", "bench_vortex",
    "bench_interp", "bench_dem", "bench_cmaes", "backend_compare",
    "bench_distributed", "bench_sim_engine", "bench_fleet", "bench_overlap",
    "bench_pencil", "bench_reuse",
)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--all", action="store_true", default=False,
                    help="run every benchmark module (the default)")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of modules to run")
    args = ap.parse_args()
    names = [n.strip() for n in args.only.split(",") if n.strip()] \
        if args.only else list(MODULES)
    unknown = [n for n in names if n not in MODULES]
    if unknown:
        ap.error(f"unknown module(s) {unknown}; known: {', '.join(MODULES)}")
    import importlib
    from repro.core import runtime as RT
    RT.enable_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for name in names:
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            for line in mod.run():
                print(line, flush=True)
        except Exception as e:  # keep sweeping; the exit code reports it
            failed.append(name)
            print(f"{name}_error,0.000,{type(e).__name__}: {e}", flush=True)
    if failed:
        print(f"benchmark modules failed: {', '.join(failed)}",
              file=sys.stderr)
    return 1 if failed else 0


if __name__ == '__main__':
    sys.exit(main())
