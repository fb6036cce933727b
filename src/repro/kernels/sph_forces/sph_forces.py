"""Fused SPH density+momentum tile kernel (paper §4.2 hot loop) — a thin
pair body over the unified cell-pair engine (``kernels/cell_pair``).

The fusion (one cubic-spline gradient evaluation feeding both the
continuity rate dρ/dt and the momentum acceleration) lives in
``apps.sph.sph_pair_body``; all pad/BlockSpec/mask/scatter plumbing lives
in the engine. The package remains for the tile-level oracle tests
(ref.py) and the jitted end-to-end op (ops.py)."""
from __future__ import annotations

from repro.apps.sph import sph_pair_body
from repro.kernels.cell_pair.cell_pair import cell_pair_pallas


def sph_cell_forces(cell_x, nbr_x, cell_v, nbr_v, cell_rho, nbr_rho,
                    cell_mask, nbr_mask, *, cfg, interpret: bool = False):
    """Tiles: (C, cc, dim)/(C, Kcc, dim) positions+velocities, (C, cc)/(C,
    Kcc) densities+masks. Returns (accel (C, cc, dim), drho (C, cc)).
    jit at the call site."""
    out = cell_pair_pallas(cell_x, nbr_x, cell_mask, nbr_mask,
                           {"v": cell_v, "rho": cell_rho},
                           {"v": nbr_v, "rho": nbr_rho},
                           body=sph_pair_body(cfg),
                           out={"a": "radial", "drho": "scalar"},
                           r_cut=cfg.r_cut,
                           interpret=interpret)
    return out["a"], out["drho"]
