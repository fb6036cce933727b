"""Lennard-Jones molecular dynamics (paper §4.1, Listing 4.1).

Reproduces the paper's MD client: particles on a periodic cubic lattice,
LJ interactions within r_cut = 3σ, velocity-Verlet integration. Energies
validate conservation (the paper's validation criterion — energy curves
identical to LAMMPS and total energy conserved).

The app is a *thin physics spec* for the simulation layer
(core/simulation.py): the LJ physics is a single ~10-line pair body
(:func:`lj_pair_body`) plus two integrator hooks, declared once in
:func:`physics`. ``make_sim_step(physics, cfg)`` runs it serially;
``make_sim_step(physics, cfg, mesh)`` runs the same spec under
``map()``/``ghost_get()`` on a device mesh — there is no distributed
version of this file. ``MDConfig.backend`` selects the ``"jnp"`` oracle
or the ``"pallas"`` VMEM pair-tile kernel on both paths.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import cell_list as CL
from repro.core import interactions as I
from repro.core import particles as P
from repro.core import simulation as SIM
from repro.numerics import integrators as TI

_LOG = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class MDConfig:
    n_per_side: int = 10           # paper: 60 (216k particles)
    sigma: float = 0.1
    epsilon: float = 1.0
    dt: float = 0.0005             # paper Listing 4.1
    box: float = 1.0
    cell_cap: int = 48
    capacity_factor: float = 1.3
    dim: int = 3
    backend: str = "jnp"               # "jnp" | "pallas" pair-engine path
    interpret: Optional[bool] = None   # pallas interpret mode (None = auto)
    precision: str = "fp32"            # "fp32" | "bf16x" pair-engine mode

    @property
    def r_cut(self) -> float:
        return 3.0 * self.sigma

    @property
    def n_particles(self) -> int:
        return self.n_per_side ** self.dim


def lj_pair_body(sigma: float, epsilon: float):
    """LJ force pair body (cell-pair engine protocol): F_ij = mag · dx."""
    s2 = sigma * sigma

    def body(dx, r2, ok, wi, wj):
        r2s = jnp.maximum(r2, 1e-12)
        inv = s2 / r2s
        inv3 = inv * inv * inv
        mag = 24.0 * epsilon * (2.0 * inv3 * inv3 - inv3) / r2s
        return {"f": I.Radial(mag)}

    return body


def physics(cfg: MDConfig) -> SIM.PhysicsSpec:
    """MD as a simulation-layer spec: velocity-Verlet around the LJ pair
    body. ``advance`` is the first kick + drift + periodic wrap (before
    migration so moved particles are re-owned); ``finish`` stores the new
    forces and applies the second kick."""
    dim = cfg.dim
    lo, hi = (0.0,) * dim, (cfg.box,) * dim

    def advance(ps, red, extras):
        ps = TI.velocity_verlet_kick(ps, cfg.dt)
        return TI.wrap_periodic(ps, lo, hi, (True,) * dim)

    def finish(ctx):
        ps = ctx.ps
        f = ctx.pair["f"][: ps.capacity]
        ps = ps.with_prop("f", jnp.where(ps.valid[:, None], f, 0.0))
        ps = TI.velocity_verlet_kick2(ps, cfg.dt)
        return ps, {}, 0

    return SIM.PhysicsSpec(
        name="md", box_lo=lo, box_hi=hi, periodic=(True,) * dim,
        r_cut=cfg.r_cut, cell_cap=cfg.cell_cap,
        pair_out={"f": "radial"},
        make_body=lambda: lj_pair_body(cfg.sigma, cfg.epsilon),
        pair_props=(), ghost_props=(),   # ghosts carry positions only
        advance=advance, finish=finish,
        backend=cfg.backend, interpret=cfg.interpret,
        precision=cfg.precision,
        bucket_cap=512, ghost_cap=1024)


# --------------------------------------------------------------------------
# Serial-convenience wrappers (the 1-slab special case of the same engine)
# --------------------------------------------------------------------------

def lj_force_kernel(cfg: MDConfig):
    """jnp ``kernel(dx, r2, wi, wj) -> force`` derived from the same pair
    body the engine runs (single-source physics)."""
    kern = I.as_jnp_kernel(lj_pair_body(cfg.sigma, cfg.epsilon),
                           {"f": "radial"}, cfg.r_cut)
    return lambda dx, r2, wi, wj: kern(dx, r2, wi, wj)["f"]


def lj_potential_kernel(cfg: MDConfig):
    s2 = cfg.sigma ** 2
    eps = cfg.epsilon
    rc2 = cfg.r_cut ** 2

    def kern(dx, r2, wi, wj):
        r2s = jnp.maximum(r2, 1e-12)
        inv3 = (s2 / r2s) ** 3
        v = 4.0 * eps * (inv3 * inv3 - inv3)
        return jnp.where(r2 < rc2, 0.5 * v, 0.0)  # half: pairs counted twice

    return kern


def init_particles(cfg: MDConfig, capacity: Optional[int] = None) -> P.ParticleSet:
    cap = capacity or int(cfg.n_particles * cfg.capacity_factor)
    ps = P.init_grid((0.0,) * cfg.dim, (cfg.box,) * cfg.dim,
                     (cfg.n_per_side,) * cfg.dim, capacity=cap,
                     prop_specs={"v": ((cfg.dim,), jnp.float32),
                                 "f": ((cfg.dim,), jnp.float32)})
    return ps


def _cl_kw(cfg: MDConfig):
    gs = CL.grid_shape_for((0.0,) * cfg.dim, (cfg.box,) * cfg.dim, cfg.r_cut)
    return dict(box_lo=(0.0,) * cfg.dim, box_hi=(cfg.box,) * cfg.dim,
                grid_shape=gs, periodic=(True,) * cfg.dim,
                cell_cap=cfg.cell_cap)


def compute_forces(ps: P.ParticleSet, cfg: MDConfig):
    cl = CL.build_cell_list(ps, **_cl_kw(cfg))
    out = I.apply_pair_kernel(ps, cl, lj_pair_body(cfg.sigma, cfg.epsilon),
                              out={"f": "radial"}, r_cut=cfg.r_cut,
                              backend=cfg.backend, interpret=cfg.interpret,
                              precision=cfg.precision)
    return ps.with_prop("f", out["f"]), cl.overflow


def md_step(ps: P.ParticleSet, cfg: MDConfig):
    """One velocity-Verlet step (Listing 4.1 lines 54-73) through the
    unified engine (serial = 1-slab path). Returns (ps, overflow)."""
    step = SIM.make_sim_step(physics, cfg)
    state, flags, _ = step(SIM.serial_state(ps, physics, cfg), {})
    return state.ps, flags.any()


@functools.partial(jax.jit, static_argnames=("cfg",))
def energies(ps: P.ParticleSet, cfg: MDConfig):
    cl = CL.build_cell_list(ps, **_cl_kw(cfg))
    pot = I.apply_kernel_cells(ps, cl, lj_potential_kernel(cfg),
                               r_cut=cfg.r_cut)
    e_pot = jnp.sum(jnp.where(ps.valid, pot, 0.0))
    v2 = jnp.sum(ps.props["v"] ** 2, axis=-1)
    e_kin = 0.5 * jnp.sum(jnp.where(ps.valid, v2, 0.0))
    return e_kin, e_pot


def init_state(cfg: MDConfig, thermal_v: float = 0.0,
               seed: int = 0) -> P.ParticleSet:
    """The paper's start (Listing 4.1): lattice positions, seeded thermal
    velocities with zero net momentum, and the initial forces."""
    ps = init_particles(cfg)
    if thermal_v > 0:
        key = jax.random.PRNGKey(seed)
        v = thermal_v * jax.random.normal(key, ps.props["v"].shape)
        # zero the net momentum over VALID particles only (averaging over
        # padding slots would leave a real net drift)
        vm = ps.valid[:, None]
        mean = (jnp.sum(jnp.where(vm, v, 0.0), axis=0, keepdims=True)
                / jnp.maximum(ps.count(), 1))
        ps = ps.with_prop("v", jnp.where(vm, v - mean, 0.0))
    ps, overflow = compute_forces(ps, cfg)
    _check_flags(overflow, "initial forces")
    return ps


def _check_flags(flags_any, where):
    if int(flags_any):
        raise RuntimeError(f"StepFlags tripped at {where}: re-provision "
                           "cell_cap / capacity")


def _log_fills(i: int, flags, fills):
    """The step's high-water marks as shares of their capacities
    (``fills``: StepFlags field -> capacity), so a capacity running short
    shows before its overflow flag trips."""
    shares = ", ".join(
        f"{k} {int(getattr(flags, k))}/{c} "
        f"({100.0 * int(getattr(flags, k)) / c:.0f}%)"
        for k, c in fills.items())
    _LOG.info("step %d: %s, candidate_pairs %d", i, shares,
              int(flags.candidate_pairs))


def run(cfg: MDConfig, n_steps: int, thermal_v: float = 0.0,
        seed: int = 0, log_every: int = 0, reuse=None, skin=None,
        mesh=None):
    """The paper's Listing 4.1 main loop. Every step's overflow/contract
    flags are checked; a tripped flag raises. Every ``log_every`` steps
    the energies are appended to the returned log and the capacity fills
    are logged (``logging``, INFO): ``cell_fill`` against ``cell_cap``,
    and on a mesh ``bucket_fill`` / ``ghost_fill`` against ``bucket_cap``
    / ``ghost_cap``.

    ``mesh`` runs the slab-decomposed step ``make_sim_step(physics, cfg,
    mesh)`` on the particles distributed over it; the returned set is then
    the sharded one, its padding slots ``valid`` False.

    ``reuse``/``skin`` select the skin-amortized engine (DESIGN.md §14):
    the cell binning is cached across steps and rebuilt only when the
    Verlet tripwire fires — same trajectory, amortized rebuild cost.

    Each step is a ``StepTraceAnnotation("md_step")`` holding the host
    spans ``md.dispatch`` and ``md.flags_read`` on the profiler's clock."""
    spec = physics(cfg)
    ps = init_state(cfg, thermal_v, seed)
    fills = {"cell_fill": spec.cell_cap}
    if mesh is None:
        state = SIM.serial_state(ps, physics, cfg)
    else:
        state = SIM.distribute(ps, physics, cfg, mesh)
        fills.update(bucket_fill=spec.bucket_cap, ghost_fill=spec.ghost_cap)
    step = SIM.make_sim_step(physics, cfg, mesh, reuse=reuse, skin=skin)
    if reuse is not None:
        state = SIM.reuse_state(state, physics, cfg, mesh, skin=skin)
    log = []
    for i in range(n_steps):
        with jax.profiler.StepTraceAnnotation("md_step", step_num=i):
            with jax.profiler.TraceAnnotation("md.dispatch"):
                state, flags, _ = step(state, {})
            with jax.profiler.TraceAnnotation("md.flags_read"):
                _check_flags(flags.any(), f"step {i}")
        if log_every and (i % log_every == 0 or i == n_steps - 1):
            ps = state.inner.ps if reuse is not None else state.ps
            ek, ep = energies(ps, cfg)
            log.append((i, float(ek), float(ep)))
            _log_fills(i, flags, fills)
    return (state.inner.ps if reuse is not None else state.ps), log
