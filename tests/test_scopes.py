"""Named layer scopes and StepFlags counters.

The chip benchmark reads each layer's device time by the ``jax.named_scope``
names in the compiled HLO's ``op_name`` metadata; each case here asserts
that one scope reaches the compiled serial MD step (both pair backends) or
the VIC step. The counters are checked against NumPy counts on a tiny
lattice. The split-phase slab step's cases run in the multi-device suite
(``tests/distributed/test_dist_scopes.py``), launched from here.
"""
from __future__ import annotations

import functools
import logging
import re

import jax.numpy as jnp
import numpy as np
import pytest

import _scope_counts as SC
from _dist_launcher import run_distributed_pytest
from repro.apps import md
from repro.apps import vortex as V
from repro.core import simulation as SIM

PAIR_SCOPES = ("cell_list", "candidate_gather", "pair_kernel",
               "slot_scatter", "advance", "finish", "counters")
VIC_SCOPES = ("m4_bucketing", "m4_p2m", "m4_m2p", "m4_unbucket", "poisson",
              "stencil", "remesh")


def _md_cfg(backend):
    return md.MDConfig(n_per_side=6, box=1.5, sigma=0.1, backend=backend)


@functools.lru_cache(maxsize=None)
def _serial_segments(backend):
    cfg = _md_cfg(backend)
    state = SIM.serial_state(md.init_particles(cfg), md.physics, cfg)
    step = SIM.make_sim_step(md.physics, cfg)
    return SC.hlo_segments(step.lower(state, {}).compile())


@functools.lru_cache(maxsize=None)
def _vic_segments():
    cfg = V.VortexConfig(shape=(16, 8, 8), use_pallas=True)
    w = V.init_ring(cfg)
    return SC.hlo_segments(V.vic_step.lower(w, cfg).compile())


@pytest.mark.parametrize("scope", PAIR_SCOPES + ("cell_pair",))
def test_serial_md_scope_pallas(scope):
    assert scope in _serial_segments("pallas")


@pytest.mark.parametrize("scope", PAIR_SCOPES)
def test_serial_md_scope_jnp(scope):
    assert scope in _serial_segments("jnp")


@pytest.mark.parametrize("scope", VIC_SCOPES)
def test_vic_scope(scope):
    segs = _vic_segments()
    assert scope in segs
    # the names the accepted benchmark readers match stay
    assert {"jit(fft_poisson)", "jit(p2m_cells)", "jit(m2p_cells)"} <= segs


@pytest.mark.parametrize("field", ["cell_fill", "candidate_pairs"])
def test_serial_counters_match_numpy(field):
    """Random positions, no velocities or forces (the step leaves them in
    place): the fullest cell and the candidate pairs of the periodic grid
    equal a NumPy binning of the same positions."""
    from repro.core import cell_list as CL
    cfg = _md_cfg("jnp")
    ps = md.init_particles(cfg)
    n = cfg.n_particles
    rng = np.random.default_rng(7)
    x = np.asarray(ps.x).copy()
    x[:n] = rng.uniform(0.0, cfg.box, (n, 3)).astype(np.float32)
    ps = ps.replace(x=jnp.asarray(x))
    state = SIM.serial_state(ps, md.physics, cfg)
    _, flags, _ = SIM.make_sim_step(md.physics, cfg)(state, {})
    gs = CL.grid_shape_for((0.0,) * 3, (cfg.box,) * 3, cfg.r_cut)
    counts = SC.counts(x[:n], 0.0, cfg.box, gs)
    want = {"cell_fill": int(counts.max()),
            "candidate_pairs": SC.candidate_pairs_np(
                counts, (True,) * 3, cfg.cell_cap)}
    assert int(getattr(flags, field)) == want[field]
    assert int(flags.any()) == 0


def test_serial_run_logs_cell_fill(caplog):
    """``md.run`` logs the cell fill as a share of ``cell_cap``; a serial
    step fills no map() bucket or ghost_get send, so it logs neither."""
    cfg = _md_cfg("jnp")
    with caplog.at_level(logging.INFO, logger=md.__name__):
        md.run(cfg, 3, thermal_v=0.5, log_every=2)
    lines = [r.getMessage() for r in caplog.records]
    assert [ln.split(":")[0] for ln in lines] == ["step 0", "step 2"]
    for ln in lines:
        m = re.search(r"cell_fill (\d+)/(\d+) \(\d+%\)", ln)
        assert m and 0 < int(m.group(1)) <= int(m.group(2)) == cfg.cell_cap
        assert "bucket_fill" not in ln and "ghost_fill" not in ln


@pytest.mark.distributed
def test_distributed_slab_scopes_and_counters():
    """Each scope of the split-phase slab step, its counters against
    NumPy (a lattice plane crossing a slab face) and ``md.run``'s fill log
    on a 4-device mesh."""
    run_distributed_pytest("tests/distributed/test_dist_scopes.py",
                           min_passed=17)
