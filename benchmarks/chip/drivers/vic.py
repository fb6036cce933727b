"""Driver of the vortex-in-cell app (``repro.apps.vortex``).

The window calls ``vortex.step_reprovision`` once per step, as
``vortex.run`` does: the compiled ``vic_step`` plus the host's read of
its overflow count. Set-up builds the paper's vortex ring, adds a small
divergence-free perturbation drawn from the seed (so seeds differ in their
fields and not in their work: every mesh node is re-seeded each step) and
warms up the step.

The check runs the plain reference (``reference/vic.py``) from each
sampled step's input field and compares the step's output field with it.
The check's control is ``ReferenceControl``: the reference with its M'4
products in bfloat16, put in the program's place (the program's own
``bf16x`` path does not compile on a v5e).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.apps import vortex as V

from reference import vic as REF

CONFIG_KEYS = ("nu", "dt", "ring_R", "ring_sigma", "gamma", "use_pallas",
               "precision", "remesh_threshold", "interp_cb")


def vic_config(config: dict) -> V.VortexConfig:
    return V.VortexConfig(shape=tuple(config["shape"]),
                          lengths=tuple(config["lengths"]),
                          **{k: config[k] for k in CONFIG_KEYS})


@functools.partial(jax.jit, static_argnames=("cfg", "amplitude"))
def _start(w, key, *, cfg, amplitude):
    """The ring plus Gaussian noise of ``amplitude`` × max|ω|, projected
    onto divergence-free fields."""
    scale = amplitude * jnp.max(jnp.abs(w))
    w = w + scale * jax.random.normal(key, w.shape, w.dtype)
    return V.project_divfree(w, cfg)


class Session:
    def __init__(self, config, traffic, workload, seed, devices):
        self.cfg = vic_config(config)
        self.config = config
        self.limits = workload["limits"]
        self.w = _start(V.init_ring(self.cfg), jax.random.PRNGKey(seed),
                        cfg=self.cfg, amplitude=float(traffic["noise"]))
        self.work_per_step = float(np.prod(self.cfg.shape))
        for _ in range(int(traffic["warmup_steps"])):
            self.step()
        self.sync()
        self.warm_cfg = self.cfg

    def step(self) -> int:
        self.w, self.cfg = V.step_reprovision(self.w, self.cfg)
        return 0

    def sync(self):
        self.w.block_until_ready()
        if self.cfg != getattr(self, "warm_cfg", self.cfg):
            raise RuntimeError("interp_cell_cap grew inside the window: "
                               f"{self.cfg.interp_cell_cap}")

    def snapshot(self):
        return self.w

    def release(self):
        self.w = None

    def hlo_texts(self):
        return [V.vic_step.lower(self.w, self.cfg).compile().as_text()]

    def check(self, samples):
        """[(name, value, limit)]: the widest gap over the sampled steps
        between the step's output vorticity and the reference's, relative
        to the reference's largest magnitude."""
        c = self.config
        err = 0.0
        for w_in, w_out in samples:
            ref = np.asarray(REF.vic_step(w_in, lengths=tuple(c["lengths"]),
                                          nu=c["nu"], dt=c["dt"]))
            out = np.asarray(w_out)
            err = max(err, float(np.abs(out - ref).max() / np.abs(ref).max()))
        return [("vort_err", err, self.limits["vort_err"])]


class ReferenceControl:
    """A session whose step is the plain reference with its M'4 products
    in bfloat16 (``reference.vic.vic_step(low=True)``) in place of the
    program's float32 step."""

    def __init__(self, sess):
        self.s = sess

    def __getattr__(self, k):
        return getattr(self.s, k)

    def step(self) -> int:
        c = self.s.config
        self.s.w = REF.vic_step(self.s.w, lengths=tuple(c["lengths"]),
                                nu=c["nu"], dt=c["dt"], low=True)
        return 0


setup = Session
control = ReferenceControl
