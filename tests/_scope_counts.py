"""Helpers of the scope and counter tests (``tests/test_scopes.py``,
``tests/distributed/test_dist_scopes.py``): the name-stack segments of a
compiled step, read by the chip benchmark's own parser
(``benchmarks/chip/scopes.py``), and the NumPy counts the StepFlags
counters are checked against.

The slab lattice has 9 planes per axis in a box of 2.25 on 4 slabs, so
the plane x = 1.125 lies on the face between slabs 1 and 2; ``slab_start``
gives that plane a velocity that carries it across the face in a step, so
``map()`` moves it.
"""
from __future__ import annotations

import os
import sys

import numpy as np

_CHIP = os.path.join(os.path.dirname(__file__), "..", "benchmarks", "chip")
if _CHIP not in sys.path:
    sys.path.insert(0, _CHIP)

import scopes  # noqa: E402

NDEV, N_SIDE, BOX, SIGMA = 4, 9, 2.25, 0.1
BUCKET_CAP, GHOST_CAP = 128, 512


def hlo_segments(compiled) -> frozenset:
    """The name-stack segments of a compiled step's HLO."""
    return scopes.hlo_segments(compiled.as_text())


def counts(x, lo, hi, gs):
    """Per-cell counts of positions ``x`` on a grid, binned in float32 as
    ``cell_list._flat_cell_of`` bins them."""
    x = np.asarray(x, np.float32)
    lo, hi = np.float32(lo), np.float32(hi)
    frac = (x - lo) / (hi - lo)
    ix = np.clip(np.floor(frac * np.asarray(gs, np.float32)).astype(int), 0,
                 np.asarray(gs) - 1)
    c = np.zeros(gs, int)
    np.add.at(c, tuple(ix.T), 1)
    return c


def candidate_pairs_np(counts, periodic, cell_cap):
    """Occupied slots of each cell times those of its 27 neighbours (self
    included), periodic axes wrapped and open ones padded with empty
    cells, summed."""
    occ = np.minimum(counts, cell_cap)
    p = occ
    for ax, per in enumerate(periodic):
        width = [(0, 0)] * occ.ndim
        width[ax] = (1, 1)
        p = np.pad(p, width, mode="wrap" if per else "constant")
    hood = sum(p[tuple(slice(o, o + n) for o, n in zip(off, occ.shape))]
               for off in np.ndindex(*(3,) * occ.ndim))
    return int(np.sum(occ * hood))


def slab_start(backend="jnp"):
    """(cfg, lattice ParticleSet, on-face mask) of the slab lattice, the
    plane on the face moving across it."""
    import jax.numpy as jnp
    from repro.apps import md
    cfg = md.MDConfig(n_per_side=N_SIDE, box=BOX, sigma=SIGMA,
                      backend=backend)
    ps = md.init_particles(cfg)
    x0 = np.asarray(ps.x)
    n = cfg.n_particles
    v0 = np.zeros_like(x0)
    on_face = np.isclose(x0[:n, 0], BOX / 2)
    v0[:n][on_face, 0] = -1.0
    return cfg, ps.with_prop("v", jnp.asarray(v0)), on_face


def expected(x0, v0, cfg, dt):
    """NumPy counts of one slab step from lattice positions ``x0`` moving
    at ``v0`` (no forces): map() bucket fill, ghost_get send fill, the
    fullest cell of the locals-only and combo lists, candidate pairs."""
    from repro.core import cell_list as CL
    rc = cfg.r_cut
    bounds = np.linspace(0.0, BOX, NDEV + 1).astype(np.float32)
    x1 = np.mod(x0 + np.float32(dt) * v0, np.float32(BOX)).astype(np.float32)
    own0 = np.clip(np.searchsorted(bounds, x0[:, 0], "right") - 1, 0,
                   NDEV - 1)
    own1 = np.clip(np.searchsorted(bounds, x1[:, 0], "right") - 1, 0,
                   NDEV - 1)
    bucket = max(int(np.sum((own0 == d) & (own1 == e)))
                 for d in range(NDEV) for e in range(NDEV) if d != e)
    lo = (-rc, 0.0, 0.0)
    hi = (BOX + rc, BOX, BOX)
    gs = CL.grid_shape_for(lo, hi, rc)
    ghost = cell = pairs = 0
    for d in range(NDEV):
        mine = x1[own1 == d]
        near_lo = mine[mine[:, 0] < bounds[d] + rc]
        near_hi = mine[mine[:, 0] >= bounds[d + 1] - rc]
        ghost = max(ghost, len(near_lo), len(near_hi))
        left, right = (d - 1) % NDEV, (d + 1) % NDEV
        from_l = x1[(own1 == left) & (x1[:, 0] >= bounds[left + 1] - rc)]
        from_r = x1[(own1 == right) & (x1[:, 0] < bounds[right] + rc)]
        from_l = from_l + np.float32([-BOX if d == 0 else 0.0, 0, 0])
        from_r = from_r + np.float32([BOX if d == NDEV - 1 else 0.0, 0, 0])
        combo = np.concatenate([mine, from_l, from_r])
        c = counts(combo, lo, hi, gs)
        cell = max(cell, int(c.max()))
        pairs = max(pairs, candidate_pairs_np(c, (False, True, True),
                                              cfg.cell_cap))
    return dict(cell_fill=cell, bucket_fill=bucket, ghost_fill=ghost,
                candidate_pairs=pairs)
