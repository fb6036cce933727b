"""Cell lists and Verlet lists (paper §2, §4.1) — dense TPU-friendly forms.

OpenFPM's cell list is a ragged bucket structure; rugged buckets do not map
onto the MXU. The TPU-native adaptation (DESIGN.md §2):

  * **CellList** — particles are binned into a Cartesian cell grid sized by
    the cutoff radius; per cell we store a *dense* (cell_cap,) slot array of
    particle indices (sentinel = ``cap``, pointing at an always-invalid
    slot). Built with one sort — O(N log N), fully on device.
  * **VerletList** — fixed-degree (k_max) neighbor matrix built from the
    cell list, with a skin radius so it is reused across steps until a
    particle moves more than skin/2 (the standard Verlet criterion).

Both carry overflow flags: exceeding cell_cap/k_max is *detected*, and the
control plane re-provisions (the same adaptation ParticleSet makes for
capacity).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .particles import ParticleSet


def grid_shape_for(box_lo, box_hi, r_cut: float,
                   skin: float = 0.0) -> Tuple[int, ...]:
    """Static cell-grid shape: cells no smaller than ``r_cut + skin`` per
    axis. A nonzero ``skin`` builds the Verlet-margined grid of the reuse
    engine (DESIGN.md §14): candidate sets drawn from the 3^dim-hood of a
    binning built at anchor positions still cover every pair within
    ``r_cut`` while no particle has moved more than ``skin/2`` since."""
    lo = np.asarray(box_lo, np.float64)
    hi = np.asarray(box_hi, np.float64)
    n = np.maximum(np.floor((hi - lo) / (r_cut + skin)).astype(int), 1)
    return tuple(int(v) for v in n)


def neighbor_offsets(dim: int) -> np.ndarray:
    """All 3^dim offsets (including zero) — the 27-neighborhood in 3D."""
    rng = [(-1, 0, 1)] * dim
    return np.stack(np.meshgrid(*rng, indexing="ij"), axis=-1).reshape(-1, dim)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CellList:
    """Dense cell list. ``cells`` has an extra trailing trash row (index
    ``n_cells``) collecting invalid particles."""

    cells: jax.Array        # (n_cells + 1, cell_cap) int32 particle indices
    counts: jax.Array       # (n_cells + 1,) int32
    cell_id: jax.Array      # (cap,) int32 flat cell per particle slot
    overflow: jax.Array     # () int32: max bucket excess over cell_cap
    fill: jax.Array         # () int32: the fullest cell's count
    grid_shape: Tuple[int, ...] = dataclasses.field(metadata=dict(static=True))
    periodic: Tuple[bool, ...] = dataclasses.field(metadata=dict(static=True))
    box_lo: Tuple[float, ...] = dataclasses.field(metadata=dict(static=True))
    box_hi: Tuple[float, ...] = dataclasses.field(metadata=dict(static=True))

    @property
    def n_cells(self) -> int:
        return int(np.prod(self.grid_shape))

    @property
    def cell_cap(self) -> int:
        return self.cells.shape[1]

    @property
    def dim(self) -> int:
        return len(self.grid_shape)


def _flat_cell_of(x, valid, box_lo, box_hi, grid_shape):
    lo = jnp.asarray(box_lo, x.dtype)
    hi = jnp.asarray(box_hi, x.dtype)
    shape = jnp.asarray(grid_shape, jnp.int32)
    n_cells = int(np.prod(grid_shape))
    frac = (x - lo) / (hi - lo)
    ix = jnp.clip(jnp.floor(frac * shape).astype(jnp.int32), 0, shape - 1)
    strides = np.concatenate([np.cumprod(grid_shape[::-1])[::-1][1:], [1]]).astype(np.int32)
    flat = jnp.sum(ix * jnp.asarray(strides), axis=-1)
    return jnp.where(valid, flat, n_cells)


@partial(jax.jit, static_argnames=("cell_cap", "grid_shape", "periodic",
                                   "box_lo", "box_hi"))
@jax.named_scope("cell_list")
def build_cell_list(ps: ParticleSet, *, box_lo, box_hi, grid_shape,
                    periodic, cell_cap: int) -> CellList:
    cap = ps.capacity
    n_cells = int(np.prod(grid_shape))
    cell_id = _flat_cell_of(ps.x, ps.valid, box_lo, box_hi, grid_shape)
    idx = jnp.arange(cap, dtype=jnp.int32)
    # the stable argsort, keeping the sorted keys (no gather for them)
    sorted_cells, order = jax.lax.sort((cell_id, idx), num_keys=1,
                                       is_stable=True)
    # rank of each particle within its cell: its sorted index less the
    # index where its run of equal keys starts, a running max over the
    # run heads (a prefix scan; no binary search, no gather)
    head = jnp.concatenate([jnp.ones((1,), bool),
                            sorted_cells[1:] != sorted_cells[:-1]])
    rank = idx - jax.lax.cummax(jnp.where(head, idx, 0))
    cells = jnp.full((n_cells + 1, cell_cap), cap, jnp.int32)
    cells = cells.at[sorted_cells, rank].set(order, mode="drop")
    counts = jnp.bincount(cell_id, length=n_cells + 1).astype(jnp.int32)
    fill = jnp.max(counts[:n_cells])
    overflow = jnp.maximum(fill - cell_cap, 0)
    return CellList(cells=cells, counts=counts, cell_id=cell_id,
                    overflow=overflow, fill=fill, grid_shape=tuple(grid_shape),
                    periodic=tuple(periodic), box_lo=tuple(box_lo),
                    box_hi=tuple(box_hi))


@jax.named_scope("counters")
def candidate_pairs(cl: CellList) -> jax.Array:
    """() int32: occupied home slots × occupied slots of their 3^dim
    neighbourhood, summed over the cells (self-pairs included): the pairs
    the cell-pair engine's candidate tiles hold, against ``3^dim·cell_cap``
    slots per home slot. The neighbourhood count is a separable box sum of
    the per-cell counts (periodic axes wrap, open axes pad with empty
    cells, as :func:`neighborhood` enumerates them). Exact below 2^31."""
    occ = jnp.minimum(cl.counts[:cl.n_cells], cl.cell_cap)
    occ = occ.reshape(cl.grid_shape)
    hood = occ
    for ax, per in enumerate(cl.periodic):
        if per:
            hood = (hood + jnp.roll(hood, 1, axis=ax)
                    + jnp.roll(hood, -1, axis=ax))
        else:
            n = hood.shape[ax]
            pad = [(0, 0)] * hood.ndim
            pad[ax] = (1, 1)
            p = jnp.pad(hood, pad)
            hood = sum(jax.lax.slice_in_dim(p, k, k + n, axis=ax)
                       for k in range(3))
    return jnp.sum(occ * hood)


def neighborhood(cl: CellList) -> Tuple[jax.Array, jax.Array]:
    """Single source for the 3^dim cell-neighborhood enumeration: returns
    (cells, shifts), consumed zipped per (cell, K-slot).

    cells  — (n_cells, 3^dim) int32 flat ids of each cell's neighborhood
             (self included); non-periodic out-of-range neighbors point at
             the trash row.
    shifts — (n_cells, 3^dim, dim) float32 box shift of each neighbor cell
             relative to the home cell's frame: a periodic neighbor reached
             by wrapping below the box carries -L, above carries +L,
             in-range neighbors carry 0. Adding the shift to a wrapped
             neighbor's particle positions makes the *direct* displacement
             from the home cell equal the periodic image displacement —
             exact for any grid size (including axes with < 3 cells, where
             the same cell appears in the neighborhood under several
             shifts)."""
    gs = np.asarray(cl.grid_shape)
    dim = cl.dim
    n_cells = cl.n_cells
    coords = np.stack(np.meshgrid(*[np.arange(s) for s in gs], indexing="ij"),
                      axis=-1).reshape(-1, dim)
    offs = neighbor_offsets(dim)                       # (K, dim)
    nb = coords[:, None, :] + offs[None, :, :]          # (n_cells, K, dim)
    flat = np.zeros(nb.shape[:2], np.int64)
    valid = np.ones(nb.shape[:2], bool)
    strides = np.concatenate([np.cumprod(gs[::-1])[::-1][1:], [1]])
    L = np.asarray(cl.box_hi) - np.asarray(cl.box_lo)
    shifts = np.zeros(nb.shape, np.float32)
    for d in range(dim):
        c = nb[..., d]
        if cl.periodic[d]:
            shifts[..., d] = (c // gs[d]) * L[d]
            c = np.mod(c, gs[d])
        else:
            valid &= (c >= 0) & (c < gs[d])
            c = np.clip(c, 0, gs[d] - 1)
        flat += c * strides[d]
    flat = np.where(valid, flat, n_cells)
    return jnp.asarray(flat, jnp.int32), jnp.asarray(shifts)


def neighborhood_cells(cl: CellList) -> jax.Array:
    """(n_cells, 3^dim) flat neighborhood ids (see :func:`neighborhood`)."""
    return neighborhood(cl)[0]


def neighborhood_shifts(cl: CellList) -> jax.Array:
    """(n_cells, 3^dim, dim) neighbor box shifts (see :func:`neighborhood`)."""
    return neighborhood(cl)[1]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class VerletList:
    """Fixed-degree neighbor matrix."""

    nbr: jax.Array        # (cap, k_max) int32 neighbor indices (cap = none)
    n_nbr: jax.Array      # (cap,) int32
    overflow: jax.Array   # () int32 max excess over k_max
    x_build: jax.Array    # positions at build time (for skin criterion)

    @property
    def k_max(self) -> int:
        return self.nbr.shape[1]


@partial(jax.jit, static_argnames=("k_max", "half"))
def build_verlet(ps: ParticleSet, cl: CellList, r_verlet: float,
                 k_max: int, half: bool = False) -> VerletList:
    """Build (cap, k_max) neighbor lists within ``r_verlet`` from a cell list.

    ``half=True`` builds the *symmetric* list (j > i only), matching the
    paper's symmetric-interaction optimization (§4.1): each pair appears
    once; contributions to j are pushed back via ghost_put-style scatter.

    Caveat: periodic images are resolved by minimum image over the listed
    index, so a grid axis needs ≥ 3 cells (otherwise a neighbor cell
    appears twice in the neighborhood and the pair is double-listed). The
    cell-tile paths (``interactions.apply_kernel_cells`` / the Pallas
    cell-pair engine) use per-neighbor-cell shifts and are exact for any
    grid size.
    """
    cap = ps.capacity
    hood = neighborhood_cells(cl)                      # (n_cells, K)
    K = hood.shape[1]
    cell_cap = cl.cell_cap
    xm = ps.masked_x()

    def per_particle(i):
        ci = cl.cell_id[i]      # ∈ [0, n_cells]; n_cells = trash (invalid)
        cand = cl.cells[hood[jnp.minimum(ci, cl.n_cells - 1)]]  # (K, cell_cap)
        cand = jnp.where(ci < cl.n_cells, cand, cap).reshape(K * cell_cap)
        xi = xm[i]
        xj = jnp.where(cand[:, None] < cap, xm[jnp.minimum(cand, cap - 1)],
                       ParticleSet.FILL)
        d = _min_image(xi - xj, cl)
        r2 = jnp.sum(d * d, axis=-1)
        ok = (r2 < r_verlet * r_verlet) & (cand != i) & (cand < cap)
        if half:
            ok &= cand > i
        # stable selection of the first k_max hits
        sel_rank = jnp.cumsum(ok) - 1
        out = jnp.full((k_max,), cap, jnp.int32)
        out = out.at[jnp.where(ok, sel_rank, k_max)].set(cand, mode="drop")
        return out, jnp.sum(ok).astype(jnp.int32)

    nbr, n_nbr = jax.lax.map(per_particle, jnp.arange(cap, dtype=jnp.int32),
                             batch_size=min(cap, 4096))
    overflow = jnp.maximum(jnp.max(n_nbr) - k_max, 0)
    return VerletList(nbr=nbr, n_nbr=n_nbr, overflow=overflow, x_build=ps.x)


def _min_image(dx: jax.Array, cl: CellList) -> jax.Array:
    """Minimum-image displacement on periodic axes."""
    lo = np.asarray(cl.box_lo)
    hi = np.asarray(cl.box_hi)
    L = jnp.asarray(hi - lo, dx.dtype)
    per = jnp.asarray(np.asarray(cl.periodic), bool)
    wrapped = dx - L * jnp.round(dx / L)
    # Guard FILL sentinels: enormous dx stays enormous on non-periodic axes.
    return jnp.where(per, jnp.where(jnp.abs(dx) < 0.6e30, wrapped, dx), dx)


def moved_beyond(x: jax.Array, x_build: jax.Array, valid: jax.Array,
                 skin: float) -> jax.Array:
    """Verlet skin criterion on raw positions: True when any valid particle
    moved more than skin/2 since ``x_build``."""
    d = x - x_build
    moved2 = jnp.sum(jnp.where(valid[:, None], d, 0.0) ** 2, axis=-1)
    return jnp.max(moved2) > (0.5 * skin) ** 2


def needs_rebuild(ps: ParticleSet, vl: VerletList, skin: float) -> jax.Array:
    """Verlet skin criterion: rebuild when any particle moved > skin/2."""
    return moved_beyond(ps.x, vl.x_build, ps.valid, skin)
