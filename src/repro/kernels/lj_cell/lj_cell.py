"""Cell-blocked Lennard-Jones forces (paper §4.1 hot loop) — a thin pair
body over the unified cell-pair engine (``kernels/cell_pair``).

Historically this file carried its own pad/BlockSpec/mask/gather/scatter
plumbing; that now lives once in the engine, and LJ is just
``apps.md.lj_pair_body`` (~10 lines of physics). The package remains for
the tile-level oracle tests (ref.py) and the jitted end-to-end op
(ops.py)."""
from __future__ import annotations

from repro.apps.md import lj_pair_body
from repro.kernels.cell_pair.cell_pair import cell_pair_pallas


def lj_cell_forces(cell_x, nbr_x, cell_mask, nbr_mask, *, sigma: float,
                   epsilon: float, r_cut: float, interpret: bool = False):
    """cell_x: (C, cc, 3); nbr_x: (C, Kcc, 3); masks: (C, cc)/(C, Kcc).
    Returns per-slot forces (C, cc, 3). Self-pairs are excluded by the
    engine's r² > 0 guard (a particle is its own neighborhood candidate at
    r=0). jit at the call site."""
    out = cell_pair_pallas(cell_x, nbr_x, cell_mask, nbr_mask,
                           body=lj_pair_body(sigma, epsilon),
                           out={"f": "radial"}, r_cut=r_cut,
                           interpret=interpret)
    return out["f"]
