"""Driver of the Lennard-Jones MD app (``repro.apps.md``).

The window calls the compiled ``make_sim_step(md.physics, cfg[, mesh],
**step)`` once per step, ``step`` being the workload's step options, and
reads its StepFlags on the host every step, as ``md.run`` does. Set-up
places the lattice, draws thermal velocities from the seed and computes
the first forces in one jitted call; on a mesh the state is scattered with
``simulation.distribute``. With ``reuse`` among the step options the state
rides in ``simulation.reuse_state`` with a cold cache, as ``md.run`` does,
so the first warm-up step takes the full path.

The check runs the plain reference (``reference/lj_md.py``) from each
sampled step's input positions and velocities and compares its forces
and velocities with the step's output, particle by particle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.apps import md
from repro.core import runtime as RT
from repro.core import simulation as SIM

from reference import lj_md as REF

CONFIG_KEYS = ("n_per_side", "sigma", "epsilon", "dt", "box", "cell_cap",
               "capacity_factor", "backend", "precision")
# the keys of a workload's ``step`` that shape the reuse engine's cached
# structure: ``reuse_state`` takes them as ``make_sim_step`` did
REUSE_STATE_KEYS = ("slab_axis", "ghost_cap", "overlap", "n_hops", "skin")


def md_config(config: dict) -> md.MDConfig:
    return md.MDConfig(**{k: config[k] for k in CONFIG_KEYS})


@functools.partial(jax.jit, static_argnames=("cfg", "thermal_v"))
def _start(ps, key, *, cfg, thermal_v):
    """Thermal velocities with zero net momentum, then the first forces."""
    vm = ps.valid[:, None]
    v = thermal_v * jax.random.normal(key, ps.props["v"].shape)
    mean = jnp.sum(jnp.where(vm, v, 0.0), axis=0) / jnp.sum(ps.valid)
    ps = ps.with_prop("v", jnp.where(vm, v - mean, 0.0))
    return md.compute_forces(ps, cfg)


class Session:
    def __init__(self, config, traffic, workload, seed, devices):
        self.cfg = md_config(config)
        self.config = config
        self.limits = workload["limits"]
        ps = md.init_particles(self.cfg)
        ps, overflow = _start(ps, jax.random.PRNGKey(seed), cfg=self.cfg,
                              thermal_v=float(traffic["thermal_v"]))
        if int(overflow):
            raise RuntimeError("cell list overflow in the first forces")
        step_kw = workload.get("step", {})
        mesh = None
        if workload.get("mesh"):
            mesh = RT.make_mesh(tuple(workload["mesh"]), ("shards",),
                                devices=devices)
            self.state = SIM.distribute(ps, md.physics, self.cfg, mesh,
                                        cap_factor=workload["cap_factor"])
        else:
            self.state = SIM.serial_state(ps, md.physics, self.cfg)
        self.fn = SIM.make_sim_step(md.physics, self.cfg, mesh, **step_kw)
        self.reuse = step_kw.get("reuse") is not None
        if self.reuse:
            self.state = SIM.reuse_state(
                self.state, md.physics, self.cfg, mesh,
                **{k: step_kw[k] for k in REUSE_STATE_KEYS if k in step_kw})
        self.work_per_step = float(self.cfg.n_particles)
        for _ in range(int(traffic["warmup_steps"])):
            if self.step():
                raise RuntimeError("StepFlags tripped in warm-up")
        self.sync()

    def step(self) -> int:
        self.state, flags, _ = self.fn(self.state, {})
        return int(int(flags.any()) > 0)

    def sync(self):
        jax.block_until_ready(self.state)

    def snapshot(self):
        ps = (self.state.inner if self.reuse else self.state).ps
        return (ps.x, ps.props["v"], ps.props["f"], ps.valid,
                ps.props.get("id"))

    def release(self):
        self.state = None

    def hlo_texts(self):
        return [self.fn.lower(self.state, {}).compile().as_text()]

    def check(self, samples):
        """[(name, value, limit)]: the widest relative gap over the sampled
        steps of the forces and of the velocities; of the positions (by
        minimum image, beyond one float32 unit in the last place at the
        box's size), relative to the reference step's largest
        displacement; and the particles missing from a step's output."""
        c = self.config
        n = self.cfg.n_particles
        f_err = v_err = pos_err = 0.0
        missing = 0
        for s_in, s_out in samples:
            x0, v0, _, ok0, id0 = (_host(a) for a in s_in)
            x1, v1, f1, ok1, id1 = (_host(a) for a in s_out)
            order_in = _order(ok0, id0)
            order_out = _order(ok1, id1)
            miss = abs(n - len(order_out))
            missing += miss
            if miss or len(order_in) != n:
                continue
            rx, rv, rf = REF.verlet_step(
                x0[order_in], v0[order_in], box=c["box"], sigma=c["sigma"],
                epsilon=c["epsilon"], r_cut=3.0 * c["sigma"], dt=c["dt"])
            f_err = max(f_err, _gap(f1[order_out], rf))
            v_err = max(v_err, _gap(v1[order_out], rv))
            box = c["box"]
            step = _image(rx - x0[order_in], box)
            # one unit in the last place of a float32 coordinate is the
            # rounding of the stored position, not a gap of the step
            gap = np.abs(_image(x1[order_out] - rx, box))
            gap = np.maximum(gap - np.spacing(np.float32(box)), 0.0)
            pos_err = max(pos_err, float(gap.max() / np.abs(step).max()))
        return [("force_err", f_err, self.limits["force_err"]),
                ("vel_err", v_err, self.limits["vel_err"]),
                ("pos_err", pos_err, self.limits["pos_err"]),
                ("missing", float(missing), 0.0)]


def _host(a):
    return None if a is None else np.asarray(a)


def _order(valid, ids):
    """Rows of the valid particles, ordered by particle id (slot order on
    the serial path, where slots do not move)."""
    rows = np.nonzero(valid)[0]
    if ids is None:
        return rows
    rows = rows[np.argsort(ids[rows], kind="stable")]
    if len(np.unique(ids[rows])) != len(rows):
        return rows[:0]
    return rows


def _image(d, box):
    return d - box * np.round(d / box)


def _gap(a, ref):
    """Widest gap over particles, relative to the reference's largest
    magnitude: max|a - ref| / max|ref|."""
    return float(np.abs(a - ref).max() / np.abs(ref).max())


setup = Session
