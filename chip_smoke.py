#!/usr/bin/env python3
"""Chip smoke run: drive the particle engine's main paths once on a TPU at
deployment size, through the entry points a user calls, and check them
against the repo's jnp oracles.

    python chip_smoke.py              # one chip: MD phase + VIC phase
    python chip_smoke.py --chips 4    # (4,) slab mesh MD vs serial, only

Phases (one chip):

* MD, the paper's §4.1 size: 60^3 = 216,000 LJ particles in a periodic
  box of 6 (lattice spacing 0.1, sigma 0.085, r_cut 0.255, 23^3 cells),
  seeded thermal velocities (``md.init_state``). 20 ``make_sim_step``
  steps on each backend keep every StepFlags field at 0, conserve total
  energy and end at the same positions; Pallas pair forces agree with the
  jnp oracle on the thermalized state; the compiled Pallas step holds a
  Mosaic kernel.
* VIC, one particle-mesh size: ``vortex.vic_step`` on a 256x64x64 mesh
  (~1M remeshed particles) with the Pallas M'4 legs agrees with the
  ``core/interp`` oracle over 2 steps, drops no particle, and its compiled
  step holds Mosaic kernels.

``--chips 4`` runs only the sharded path: the MD config on a (4,) slab mesh
(``SIM.distribute`` + ``make_sim_step(md.physics, cfg, mesh)``, Pallas),
10 steps against the same 10 steps run serially on one of those chips.

Every result line names what it measured; the last line of standard output
is one JSON object ``{"ok": true, "device": {...}}``. Any failed check or
exception exits nonzero before that line is printed. The script refuses to
run without a TPU (``JAX_PLATFORMS=cpu`` included).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# bounds stated for the checks below
FORCE_TOL = 1e-4    # Pallas vs jnp forces, max-abs relative (backend_compare)
TRAJ_TOL = 1e-4     # max |dx| after the steps (test_dist_equivalence bound)
DRIFT_TOL = 1e-3    # |E_end - E_0| / |E_0| over 20 velocity-Verlet steps
VIC_TOL = 1e-4      # Pallas vs oracle vorticity, max-abs relative


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def rel(a, b) -> float:
    import jax.numpy as jnp
    return float(jnp.abs(a - b).max()) / (float(jnp.abs(b).max()) + 1e-9)


def compile_step(step, *args):
    """(compiled, seconds): compile a jitted entry point for ``args``."""
    t0 = time.perf_counter()
    compiled = step.lower(*args).compile()
    return compiled, time.perf_counter() - t0


def has_mosaic(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def flags_zero(flags) -> bool:
    import jax
    return all(int(v) == 0 for v in jax.tree.leaves(flags))


def md_config(backend: str):
    from repro.apps import md
    return md.MDConfig(n_per_side=60, sigma=0.085, box=6.0, dt=0.0005,
                       backend=backend, interpret=False)


def md_run(cfg, ps, n_steps: int, mesh=None, **step_kw):
    """Step ``ps`` ``n_steps`` times through ``make_sim_step``; returns
    (final state, compile s, steady s/step). Flags are checked every
    step."""
    import jax
    from repro.apps import md
    from repro.core import simulation as SIM
    step = SIM.make_sim_step(md.physics, cfg, mesh, **step_kw)
    if mesh is None:
        state = SIM.serial_state(ps, md.physics, cfg)
    else:
        state = SIM.distribute(ps, md.physics, cfg, mesh, cap_factor=1.5)
        spread = {d.id for d in state.ps.x.sharding.device_set}
        check(len(spread) == mesh.size,
              f"distributed state spread over {mesh.size} devices: {spread}")
    compiled, t_comp = compile_step(step, state, {})
    check(has_mosaic(compiled) or cfg.backend != "pallas",
          f"{cfg.backend} step holds a Mosaic kernel (tpu_custom_call)")
    state, flags, _ = compiled(state, {})             # warm-up step
    check(flags_zero(flags), f"StepFlags all 0 at step 0: {flags}")
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for i in range(1, n_steps):
        state, flags, _ = compiled(state, {})
        check(flags_zero(flags), f"StepFlags all 0 at step {i}: {flags}")
    jax.block_until_ready(state)
    t_step = (time.perf_counter() - t0) / max(n_steps - 1, 1)
    return state, t_comp, t_step


def phase_md() -> None:
    import jax.numpy as jnp
    from repro.apps import md
    cfg_j, cfg_p = md_config("jnp"), md_config("pallas")
    t0 = time.perf_counter()
    ps0 = md.init_state(cfg_j, thermal_v=0.3, seed=0)
    ps0.x.block_until_ready()
    log(f"md: n={cfg_j.n_particles} capacity={ps0.capacity} "
        f"r_cut={cfg_j.r_cut} init_s={time.perf_counter() - t0:.3f}")

    e0 = sum(float(e) for e in md.energies(ps0, cfg_j))
    finals = {}
    for cfg in (cfg_j, cfg_p):
        state, t_comp, t_step = md_run(cfg, ps0, 20)
        ps = state.ps
        e1 = sum(float(e) for e in md.energies(ps, cfg_j))
        drift = abs(e1 - e0) / abs(e0)
        log(f"md[{cfg.backend}]: compile_s={t_comp:.3f} "
            f"step_s={t_step:.6f} steps=20 flags=0 E0={e0:.6e} "
            f"E20={e1:.6e} drift={drift:.3e} (bound {DRIFT_TOL})")
        check(drift <= DRIFT_TOL, f"md[{cfg.backend}] energy drift {drift}")
        finals[cfg.backend] = ps
    dx = float(jnp.abs(finals["pallas"].x - finals["jnp"].x).max())
    log(f"md: 20-step positions pallas vs jnp max|dx|={dx:.3e} "
        f"(bound {TRAJ_TOL})")
    check(dx <= TRAJ_TOL, f"md trajectories max|dx| {dx} <= {TRAJ_TOL}")
    # forces of both backends on one state: the lattice start is useless
    # for this (its forces cancel to roundoff by symmetry), so use the
    # thermalized state the jnp trajectory reached
    ps = finals["jnp"]
    f_j = md.compute_forces(ps, cfg_j)[0].props["f"]
    f_p = md.compute_forces(ps, cfg_p)[0].props["f"]
    r = rel(f_p, f_j)
    log(f"md: step-20 state forces pallas vs jnp rel={r:.3e} "
        f"(max|f|={float(jnp.abs(f_j).max()):.4e}, bound {FORCE_TOL})")
    check(r <= FORCE_TOL, f"forces rel {r} <= {FORCE_TOL}")


def phase_vic() -> None:
    import jax
    from repro.apps import vortex as V
    cfg_p = V.VortexConfig(shape=(256, 64, 64), use_pallas=True)
    cfg_j = dataclasses.replace(cfg_p, use_pallas=False)
    w0 = V.project_divfree(V.init_ring(cfg_p), cfg_p)
    log(f"vic: shape={cfg_p.shape} lengths={cfg_p.lengths} "
        f"spacing={[L / n for n, L in zip(cfg_p.shape, cfg_p.lengths)]}")
    finals = {}
    for cfg in (cfg_j, cfg_p):
        compiled, t_comp = compile_step(V.vic_step, w0, cfg)
        if cfg.use_pallas:
            check(has_mosaic(compiled),
                  "VIC Pallas step holds Mosaic kernels (tpu_custom_call)")
        w, ovf = compiled(w0)
        check(int(ovf) == 0, f"vic step 0 overflow {int(ovf)}")
        jax.block_until_ready(w)
        t0 = time.perf_counter()
        w, ovf = compiled(w)
        jax.block_until_ready(w)
        t_step = time.perf_counter() - t0
        check(int(ovf) == 0, f"vic step 1 overflow {int(ovf)}")
        name = "pallas" if cfg.use_pallas else "jnp"
        log(f"vic[{name}]: compile_s={t_comp:.3f} step_s={t_step:.6f} "
            f"steps=2 overflow=0")
        finals[name] = w
    r = rel(finals["pallas"], finals["jnp"])
    log(f"vic: 2-step vorticity pallas vs oracle rel={r:.3e} "
        f"(bound {VIC_TOL})")
    check(r <= VIC_TOL, f"vic rel {r} <= {VIC_TOL}")


def phase_md_sharded(ndev: int) -> None:
    import jax
    import numpy as np
    from repro.apps import md
    from repro.core import runtime as RT
    cfg = md_config("pallas")
    mesh = RT.make_mesh((ndev,), ("shards",), devices=jax.devices()[:ndev])
    ps0 = md.init_state(cfg, thermal_v=0.3, seed=0)
    # each side of a slab face holds ~ box^2 * r_cut * density ≈ 9,200
    # ghosts; provision ghost_get with margin
    kw = dict(ghost_cap=12288)
    ref, t_cs, t_ss = md_run(cfg, ps0, 10)
    log(f"md_sharded[serial]: compile_s={t_cs:.3f} step_s={t_ss:.6f} "
        f"steps=10 flags=0 device={jax.devices()[0]}")
    state, t_cd, t_sd = md_run(cfg, ps0, 10, mesh=mesh, **kw)
    ps = state.ps
    spread = sorted({d.id for d in ps.x.sharding.device_set})
    log(f"md_sharded[{ndev}]: compile_s={t_cd:.3f} step_s={t_sd:.6f} "
        f"steps=10 flags=0 x.sharding={ps.x.sharding} devices={spread}")
    check(len(spread) == ndev, f"state spread over {ndev} devices: {spread}")
    val = np.asarray(ps.valid)
    ids = np.asarray(ps.props["id"])
    check(int(val.sum()) == cfg.n_particles, "no particle lost")
    dx = float(np.abs(np.asarray(ps.x)[val]
                      - np.asarray(ref.ps.x)[ids[val]]).max())
    log(f"md_sharded: {ndev}-slab vs serial max|dx|={dx:.3e} "
        f"(bound {TRAJ_TOL})")
    check(dx <= TRAJ_TOL, f"sharded max|dx| {dx} <= {TRAJ_TOL}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the (4,) slab-mesh MD phase")
    args = ap.parse_args()

    from repro.core import runtime as RT
    cache = RT.enable_compile_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (platform={devs[0].platform}); refusing "
              "to run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 2
    log(f"device: {devs[0].device_kind} x{len(devs)} jax={jax.__version__} "
        f"compile_cache={cache}")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_md_sharded(4)
    else:
        phase_md()
        phase_vic()
    log(f"total_s={time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
