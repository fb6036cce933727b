"""Named layer scopes and StepFlags counters of the split-phase slab step,
on a 4-device submesh (the lattice of ``tests/_scope_counts.py``: a plane
on the face between slabs 1 and 2 crosses it in the step).

The chip benchmark reads each layer's device time by its scope in the
compiled HLO's ``op_name`` metadata; each case asserts that one scope
reaches the compiled slab step. The counters are checked against NumPy's
counts of the busiest device, and ``md.run`` on the mesh is checked to log
the fills that only a mesh step fills.
"""
import logging
import re

import numpy as np
import pytest

import _scope_counts as SC
from benchmarks import dist_common as DC
from repro.apps import md
from repro.core import simulation as SIM

PAIR_SCOPES = ("cell_list", "candidate_gather", "pair_kernel",
               "slot_scatter", "advance", "finish", "counters")
SLAB_SCOPES = PAIR_SCOPES + ("pair_interior", "pair_boundary", "map",
                             "ghost_get", "cell_pair")
CAPS = dict(bucket_cap=SC.BUCKET_CAP, ghost_cap=SC.GHOST_CAP)


@pytest.fixture(scope="module")
def mesh4():
    return DC.make_submesh(SC.NDEV)


@pytest.fixture(scope="module")
def slab_segments(mesh4):
    """Segments of the compiled slab step on the Pallas path (interpret
    mode off the chip)."""
    cfg, ps, _ = SC.slab_start("pallas")
    state = SIM.distribute(ps, md.physics, cfg, mesh4)
    step = SIM.make_sim_step(md.physics, cfg, mesh4, **CAPS)
    return SC.hlo_segments(step.lower(state, {}).compile())


@pytest.fixture(scope="module")
def slab_counts(mesh4):
    """(StepFlags of one jnp slab step, NumPy's counts, plane size)."""
    cfg, ps, on_face = SC.slab_start("jnp")
    n = cfg.n_particles
    state = SIM.distribute(ps, md.physics, cfg, mesh4)
    _, flags, _ = SIM.make_sim_step(md.physics, cfg, mesh4, **CAPS)(state,
                                                                     {})
    want = SC.expected(np.asarray(ps.x)[:n], np.asarray(ps.props["v"])[:n],
                       cfg, cfg.dt)
    return flags, want, int(on_face.sum())


@pytest.mark.parametrize("scope", SLAB_SCOPES)
def test_slab_scope(slab_segments, scope):
    assert scope in slab_segments


@pytest.mark.parametrize("field", ["cell_fill", "bucket_fill", "ghost_fill",
                                   "candidate_pairs"])
def test_slab_counters_match_numpy(slab_counts, field):
    """The fullest map() bucket is the plane's crossing half, and every
    fill and the candidate pairs equal NumPy's counts of the busiest
    device."""
    flags, want, on_face = slab_counts
    assert on_face == SC.N_SIDE ** 2
    assert int(getattr(flags, field)) == want[field], (field, want)
    assert want["bucket_fill"] == on_face
    assert int(flags.bucket) == int(flags.ghost) == 0


def test_run_logs_mesh_fills(mesh4, caplog):
    """``md.run`` on the mesh logs the map() bucket and ghost_get fills
    as nonzero shares of the physics' capacities (in the first step, half
    the plane on the face crosses it)."""
    cfg, _, _ = SC.slab_start("jnp")
    with caplog.at_level(logging.INFO, logger=md.__name__):
        md.run(cfg, 1, thermal_v=0.5, seed=3, log_every=1, mesh=mesh4)
    lines = [r.getMessage() for r in caplog.records]
    assert len(lines) == 1, lines
    spec = md.physics(cfg)
    for field, cap in (("bucket_fill", spec.bucket_cap),
                       ("ghost_fill", spec.ghost_cap),
                       ("cell_fill", spec.cell_cap)):
        m = re.search(rf"{field} (\d+)/(\d+) \((\d+)%\)", lines[0])
        assert m and int(m.group(2)) == cap, lines
        assert 0 < int(m.group(1)) <= cap and int(m.group(3)) > 0, lines
