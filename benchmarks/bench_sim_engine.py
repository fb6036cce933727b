"""Before/after comparison for the simulation-layer refactor: the unified
``make_sim_step`` engine vs frozen copies of the pre-refactor step
implementations (the hand-rolled serial steps and the deleted
``md_distributed``/``sph_distributed`` twins), MD + SPH.

The legacy implementations are kept HERE, verbatim-in-spirit and clearly
frozen, precisely so this comparison survives the twins' deletion: the
acceptance bar for the refactor is unified-engine step time within 5% of
the pre-refactor apps (the engine compiles to the same fused step, so the
ratio should be ~1.0).

Rows: ``sim_engine_{md,sph}_{serial,dist8}`` — us_per_call is the ENGINE
time; ``derived`` carries the legacy time and the ratio. Distributed rows
run in a ``--child`` subprocess with 8 forced host devices (same pattern
as bench_distributed).
"""
import functools
import os
import sys

import pathlib

_ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(_ROOT / "src"), str(_ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.xla_env import ensure_forced_host_devices

N_STEPS_MD = 10      # serial trajectory steps per timing sample
GATE = 1.10          # standalone-gate ratio (report target is 1.05; the
#                      extra slack absorbs shared-CPU timing noise)


# --------------------------------------------------------------------------
# Frozen pre-refactor steps (DO NOT "fix" these — they are the baseline)
# --------------------------------------------------------------------------

def _legacy_md_serial(cfg):
    import jax
    import jax.numpy as jnp
    from repro.apps import md
    from repro.core import cell_list as CL
    from repro.core import interactions as I
    from repro.numerics import integrators as TI

    body = md.lj_pair_body(cfg.sigma, cfg.epsilon)
    cl_kw = md._cl_kw(cfg)

    @jax.jit
    def step(ps):
        ps = TI.velocity_verlet_kick(ps, cfg.dt)
        ps = TI.wrap_periodic(ps, (0.0,) * cfg.dim, (cfg.box,) * cfg.dim,
                              (True,) * cfg.dim)
        cl = CL.build_cell_list(ps, **cl_kw)
        f = I.apply_pair_kernel(ps, cl, body, out={"f": "radial"},
                                r_cut=cfg.r_cut)["f"]
        ps = ps.with_prop("f", jnp.where(ps.valid[:, None], f, 0.0))
        ps = TI.velocity_verlet_kick2(ps, cfg.dt)
        return ps, cl.overflow

    return step


def _legacy_sph_serial(cfg):
    import jax
    import jax.numpy as jnp
    from repro.apps import sph

    @jax.jit
    def step(ps, euler):
        a, drho, overflow = sph.compute_rates(ps, cfg)
        amax = jnp.max(jnp.where(ps.valid, jnp.linalg.norm(a, axis=-1), 0.0))
        dt = cfg.cfl * jnp.minimum(
            jnp.sqrt(cfg.h / jnp.maximum(amax, 1e-6)), cfg.h / cfg.c_sound)
        v, v_prev = ps.props["v"], ps.props["v_prev"]
        rho, rho_prev = ps.props["rho"], ps.props["rho_prev"]
        fluid = (ps.props["kind"] == sph.FLUID)[:, None]
        v_new = jnp.where(euler, v + dt * a, v_prev + 2.0 * dt * a)
        rho_new = jnp.where(euler, rho + dt * drho,
                            rho_prev + 2.0 * dt * drho)
        x_new = ps.x + jnp.where(fluid, dt * v + 0.5 * dt * dt * a, 0.0)
        eps = cfg.dp * 0.5
        x_new = jnp.clip(x_new, eps, jnp.asarray(cfg.box, jnp.float32) - eps)
        rho_new = jnp.maximum(rho_new, 0.9 * cfg.rho0)
        vm = ps.valid[:, None]
        ps = ps.replace(x=jnp.where(vm, x_new, ps.x))
        ps = ps.with_prop("v", jnp.where(fluid & vm, v_new, 0.0))
        ps = ps.with_prop("v_prev", v)
        ps = ps.with_prop("rho", jnp.where(ps.valid, rho_new, rho))
        ps = ps.with_prop("rho_prev", rho)
        return ps, dt, overflow

    return step


def _legacy_md_dist(mesh, cfg, example, axis_name="shards",
                    bucket_cap=512, ghost_cap=1024):
    """Frozen apps/md_distributed.make_distributed_step."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.apps.md import lj_pair_body
    from repro.core import cell_list as CL
    from repro.core import interactions as I
    from repro.core import mappings as M
    from repro.core import particles as PS
    from repro.core import runtime as RT
    from repro.numerics import integrators as TI

    spec = M.ps_specs(example, axis_name)
    body = lj_pair_body(cfg.sigma, cfg.epsilon)
    lo = (-cfg.r_cut,) + (0.0,) * (cfg.dim - 1)
    hi = (cfg.box + cfg.r_cut,) + (cfg.box,) * (cfg.dim - 1)
    gs = CL.grid_shape_for(lo, hi, cfg.r_cut)
    cl_kw = dict(box_lo=lo, box_hi=hi, grid_shape=gs,
                 periodic=(False,) + (True,) * (cfg.dim - 1),
                 cell_cap=cfg.cell_cap)

    def local_step(ps, bounds):
        ps = TI.velocity_verlet_kick(ps, cfg.dt)
        ps = TI.wrap_periodic(ps, (0.0,) * cfg.dim, (cfg.box,) * cfg.dim,
                              (True,) * cfg.dim)
        ps, ovf_map, _ = M.map_particles_local(ps, bounds, axis_name,
                                               bucket_cap)
        ghosts, ovf_g, _ = M.ghost_get_local(
            ps, bounds, cfg.r_cut, axis_name, ghost_cap, periodic=True,
            box_len=cfg.box, prop_names=())
        gp = ghosts.as_particles()
        combo = PS.ParticleSet(
            x=jnp.concatenate([ps.x, gp.x]), props={},
            valid=jnp.concatenate([ps.valid, gp.valid]))
        cl = CL.build_cell_list(combo, **cl_kw)
        f = I.apply_pair_kernel(combo, cl, body, out={"f": "radial"},
                                r_cut=cfg.r_cut)["f"]
        f_local = f[: ps.capacity]
        ps = ps.with_prop("f", jnp.where(ps.valid[:, None], f_local, 0.0))
        ps = TI.velocity_verlet_kick2(ps, cfg.dt)
        overflow = jnp.maximum(jnp.maximum(ovf_map, ovf_g),
                               RT.pmax(cl.overflow, axis_name))
        return ps, overflow

    stepped = RT.shard_map(local_step, mesh, in_specs=(spec, P()),
                           out_specs=(spec, P()), check_vma=False)
    return jax.jit(stepped)


def _legacy_sph_dist(mesh, cfg, example, axis_name="shards",
                     bucket_cap=2048, ghost_cap=2048):
    """Frozen apps/sph_distributed.make_distributed_step."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.apps import sph
    from repro.core import cell_list as CL
    from repro.core import interactions as I
    from repro.core import mappings as M
    from repro.core import particles as PS
    from repro.core import runtime as RT

    spec = M.ps_specs(example, axis_name)
    body = sph.sph_pair_body(cfg)
    lo = (-cfg.r_cut,) + (0.0,) * (cfg.dim - 1)
    hi = (cfg.box[0] + cfg.r_cut,) + tuple(cfg.box[1:])
    gs = CL.grid_shape_for(lo, hi, cfg.r_cut)
    cl_kw = dict(box_lo=lo, box_hi=hi, grid_shape=gs,
                 periodic=(False,) * cfg.dim, cell_cap=cfg.cell_cap)
    ghost_props = ("v", "rho", "kind")

    def local_step(ps, bounds, euler):
        ghosts, ovf_g, _ = M.ghost_get_local(
            ps, bounds, cfg.r_cut, axis_name, ghost_cap, periodic=False,
            box_len=float(cfg.box[0]), prop_names=ghost_props)
        gp = ghosts.as_particles()
        combo = PS.ParticleSet(
            x=jnp.concatenate([ps.x, gp.x]),
            props={k: jnp.concatenate([ps.props[k], gp.props[k]])
                   for k in ghost_props},
            valid=jnp.concatenate([ps.valid, gp.valid]))
        cl = CL.build_cell_list(combo, **cl_kw)
        out = I.apply_pair_kernel(combo, cl, body,
                                  out={"a": "radial", "drho": "scalar"},
                                  r_cut=cfg.r_cut, prop_names=("v", "rho"))
        n = ps.capacity
        grav = jnp.zeros((cfg.dim,), jnp.float32).at[-1].set(-cfg.g)
        fluid = ps.props["kind"] == sph.FLUID
        a = jnp.where(fluid[:, None], out["a"][:n] + grav, 0.0)
        drho = out["drho"][:n]
        amax = jnp.max(jnp.where(ps.valid, jnp.linalg.norm(a, axis=-1), 0.0))
        amax = RT.pmax(amax, axis_name)
        dt = cfg.cfl * jnp.minimum(jnp.sqrt(cfg.h / jnp.maximum(amax, 1e-6)),
                                   cfg.h / cfg.c_sound)
        v, v_prev = ps.props["v"], ps.props["v_prev"]
        rho, rho_prev = ps.props["rho"], ps.props["rho_prev"]
        fl = fluid[:, None]
        v_new = jnp.where(euler, v + dt * a, v_prev + 2 * dt * a)
        rho_new = jnp.where(euler, rho + dt * drho, rho_prev + 2 * dt * drho)
        x_new = ps.x + jnp.where(fl, dt * v + 0.5 * dt * dt * a, 0.0)
        eps = cfg.dp * 0.5
        x_new = jnp.clip(x_new, eps, jnp.asarray(cfg.box, jnp.float32) - eps)
        rho_new = jnp.maximum(rho_new, 0.9 * cfg.rho0)
        vm = ps.valid[:, None]
        ps = ps.replace(x=jnp.where(vm, x_new, ps.x))
        ps = ps.with_prop("v", jnp.where(fl & vm, v_new, 0.0))
        ps = ps.with_prop("v_prev", v)
        ps = ps.with_prop("rho", jnp.where(ps.valid, rho_new, rho))
        ps = ps.with_prop("rho_prev", rho)
        ps, ovf_m, _ = M.map_particles_local(ps, bounds, axis_name,
                                             bucket_cap)
        overflow = jnp.maximum(jnp.maximum(ovf_g, ovf_m),
                               RT.pmax(cl.overflow, axis_name))
        return ps, dt, overflow

    stepped = RT.shard_map(
        local_step, mesh, in_specs=(spec, P(), P()),
        out_specs=(spec, P(), P()), check_vma=False)
    return jax.jit(stepped)


# --------------------------------------------------------------------------
# Comparisons
# --------------------------------------------------------------------------

def _compare_rows():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks import dist_common as DC
    from benchmarks.common import time_fn
    from repro.apps import md, sph
    from repro.core import simulation as SIM

    rows = []
    # generous warmup + median-of-9: the serial steps are ~10-30 ms on the
    # CPU host and cache-cold first calls easily fake a >5% "regression"
    time_fn = functools.partial(time_fn, warmup=4, iters=9)

    def emit(name, sec_engine, sec_legacy):
        ratio = sec_engine / sec_legacy
        rows.append(
            f"sim_engine_{name},{sec_engine * 1e6:.1f},"
            f"legacy_us={sec_legacy * 1e6:.1f};ratio={ratio:.3f}"
            f";gate<={GATE:.2f}")

    # serial MD
    cfg = md.MDConfig(n_per_side=10, sigma=0.085)
    ps0, _ = DC.md_serial_start(cfg)
    legacy = _legacy_md_serial(cfg)
    engine = SIM.make_sim_step(md.physics, cfg)
    state0 = SIM.serial_state(ps0, md.physics, cfg)
    sec_l, _ = time_fn(lambda p: legacy(p)[0], ps0)
    sec_e, _ = time_fn(lambda s: engine(s, {})[0], state0)
    emit("md_serial", sec_e, sec_l)

    # serial SPH
    scfg = DC.sph_config()
    sps = sph.init_dam_break(scfg)
    slegacy = _legacy_sph_serial(scfg)
    sengine = SIM.make_sim_step(sph.physics, scfg)
    sstate = SIM.serial_state(sps, sph.physics, scfg)
    ex = {"euler": jnp.asarray(False)}
    sec_l, _ = time_fn(lambda p: slegacy(p, ex["euler"])[0], sps)
    sec_e, _ = time_fn(lambda s: sengine(s, ex)[0], sstate)
    emit("sph_serial", sec_e, sec_l)

    if jax.device_count() >= 8:
        ndev = 8
        mesh = DC.make_submesh(ndev)
        # distributed MD (the deleted md_distributed twin as baseline)
        dcfg = DC.md_config(n_per_side=10, sigma=0.04)
        dstate = DC.md_distributed_start(mesh, dcfg, ndev, cap_per_dev=256)
        dlegacy = _legacy_md_dist(mesh, dcfg, dstate.ps)
        dengine = SIM.make_sim_step(md.physics, dcfg, mesh, axis_name=DC.AXIS)
        sec_l, _ = time_fn(lambda: dlegacy(dstate.ps, dstate.bounds)[0])
        sec_e, _ = time_fn(lambda: dengine(dstate, {})[0])
        emit("md_dist8", sec_e, sec_l)

        # distributed SPH (the deleted sph_distributed twin as baseline)
        dscfg = DC.sph_config()
        dsstate, _ = DC.sph_distributed_start(mesh, dscfg, ndev)
        dslegacy = _legacy_sph_dist(mesh, dscfg, dsstate.ps)
        dsengine = SIM.make_sim_step(sph.physics, dscfg, mesh,
                                     axis_name=DC.AXIS)
        eu = jnp.asarray(False)
        sec_l, _ = time_fn(
            lambda: dslegacy(dsstate.ps, dsstate.bounds, eu)[0])
        sec_e, _ = time_fn(lambda: dsengine(dsstate, {"euler": eu})[0])
        emit("sph_dist8", sec_e, sec_l)

    return rows


def _child_main():
    ensure_forced_host_devices(os.environ)
    for r in _compare_rows():
        print(r, flush=True)


def run():
    """Parent entry (benchmarks/run.py): relay the child's CSV rows."""
    from benchmarks.xla_env import run_forced_host_child
    return run_forced_host_child(__file__, "sim_engine_")


def main() -> int:
    """Standalone gate: engine/legacy ratio must stay under GATE."""
    ok = True
    for line in run():
        name, us, derived = line.split(",", 2)
        ratio = float(derived.split("ratio=")[1].split(";")[0])
        status = "OK" if ratio <= GATE else "FAIL"
        print(f"{name}: engine {float(us):.0f} us, {derived} [{status}]")
        ok &= ratio <= GATE
    if not ok:
        print(f"unified engine regressed beyond {GATE:.2f}x legacy",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    if "--child" in sys.argv:
        _child_main()
    else:
        sys.exit(main())
