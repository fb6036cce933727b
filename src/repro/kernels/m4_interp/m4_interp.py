"""Cell-bucketed M'4 particle–mesh interpolation Pallas TPU kernels
(paper §2/§4.4 hot loop — the vortex-in-cell interpolation + remeshing path).

The TPU-native adaptation (DESIGN.md §2, §7): scatter-adds do not map onto
the MXU, so P2M is re-formulated as a *conflict-free owner-gather*.
Particles are pre-bucketed by the existing ``CellList`` into interpolation
cells of ``cb`` mesh nodes per axis (cell size = cb·h). Each Pallas grid
step then *owns* one disjoint ``cb^dim`` node patch of the output field and
pulls every contribution from the 3^dim surrounding particle buckets —
because the M'4 support is 2h and cb ≥ 2, those buckets are exactly the
particles that can reach the patch. No two grid steps write the same node,
so no atomics / serialization are needed.

Neighbor buckets are *not* materialized 27× in HBM (the lj_cell pre-gather
trade-off): the dense (cell, slot) tiles are passed 3^dim times with
wrapped index_maps — the stencil7 halo trick applied to particle tiles.
Per neighbor the kernel evaluates the separable per-axis M'4 weights on the
VPU over the (cb^dim, cell_cap) pair-weight tile and accumulates
``weights @ values`` on the MXU at full f32 precision; one write to the
output block at the end. Operands are laid out for the TPU tiling rule:
bucket positions/values/masks component-major ``(·, cell_cap)`` per cell,
field patches ``(C, cb^dim)``, so every block's last two dims are full
array dims and no kernel slice runs along the lane axis.

M2P is the transpose: each grid step owns one particle bucket, walks the
3^dim neighboring *field* blocks (again wrapped index_maps, stencil7-style)
and accumulates ``weights @ field_block`` — velocity and RHS ride in one
fused channel axis, so the weight tile is computed once for both.

Both kernels are periodic-only (the clamped non-periodic edge semantics of
the oracle stay on the jnp path) and run with ``interpret=True`` off-TPU.
Weights are evaluated from raw positions — w = Π_d M'4((x_d − node_d)/h_d)
with the periodic image resolved per neighbor tile from the grid index, so
the kernel needs no floor/frac bookkeeping and matches ``core/interp.py``
to f32 rounding.
"""
from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.interp import m4_prime


def _offsets(dim: int):
    return list(itertools.product((-1, 0, 1), repeat=dim))


def _node_coords(cb: int, dim: int):
    """Per-axis local node index of each of the cb^dim patch nodes, as
    (cb^dim, 1) f32 columns in C order (node n = Σ_d a_d·cb^(dim-1-d)).
    Built from a 2-D int32 iota (TPU forbids 1-D and float iota) with
    +0.5-guarded f32 floors, exact for any cb without an integer divide."""
    n = jax.lax.broadcasted_iota(jnp.int32, (cb ** dim, 1), 0
                                 ).astype(jnp.float32)
    cols = []
    for d in range(dim):
        q = jnp.floor((n + 0.5) * (1.0 / cb ** (dim - 1 - d)))
        cols.append(q - cb * jnp.floor((q + 0.5) * (1.0 / cb)))
    return cols


def _weights(x_ref, m_ref, cells, node_cols, shifts, cb, lo, h):
    """(cb^dim, cc) M'4 weight tile of one particle bucket against the
    patch of nodes whose first node is ``cells[d]·cb`` per axis:
    w[n, p] = mask_p · Π_d M'4((node_d(n) − x_d(p) − shift_d) / h_d).
    Positions come component-major, one (1, cc) lane row per axis."""
    dim = len(cells)
    lead = (0,) * dim
    w = m_ref[lead + (pl.ds(0, 1), slice(None))].astype(jnp.float32)
    for d in range(dim):
        xd = x_ref[lead + (pl.ds(d, 1), slice(None))]           # (1, cc)
        nodes = (cells[d] * cb + node_cols[d]) * h[d] + lo[d]   # (cb^dim, 1)
        w = w * m4_prime((nodes - xd - shifts[d]) / h[d])
    return w


def _dot(a, b, contract, precision):
    """f32 MXU product at full precision (bf16 operands in bf16x mode)."""
    if precision == "bf16x":   # bf16 operands, fp32 MXU accumulate
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    return jax.lax.dot_general(
        a, b, (contract, ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)


def _p2m_kernel(*refs, offsets, grid_cells, cb, lo, h, lengths,
                precision="fp32"):
    dim = len(grid_cells)
    K = len(offsets)
    x_refs, v_refs, m_refs = refs[:K], refs[K:2 * K], refs[2 * K:3 * K]
    o_ref = refs[3 * K]
    node_cols = _node_coords(cb, dim)
    lead = (0,) * dim
    acc = jnp.zeros(o_ref.shape[dim:], jnp.float32)        # (cb^dim, n_ch)
    for n, off in enumerate(offsets):
        cells, shifts = [], []
        for d in range(dim):
            cell = pl.program_id(d) + off[d]
            # periodic image of this neighbor bucket (data comes in wrapped
            # by the index_map; positions must be unwrapped to match)
            shifts.append(jnp.where(cell < 0, -lengths[d],
                                    jnp.where(cell >= grid_cells[d],
                                              lengths[d], 0.0)
                                    ).astype(jnp.float32))
            cells.append(pl.program_id(d))
        w = _weights(x_refs[n], m_refs[n], cells, node_cols, shifts, cb,
                     lo, h)                                 # (cb^dim, cc)
        acc = acc + _dot(w, v_refs[n][lead], ((1,), (1,)), precision)
    o_ref[lead] = acc


def _blocked(shape, cb):
    """Mesh axes (N_0..N_{dim-1}) <-> (g_0..g_{dim-1}, cb^dim) node-patch
    blocks: the split shape and the transpose that put each patch's nodes
    on one kernel block axis."""
    dim = len(shape)
    split = tuple(v for n in shape for v in (n // cb, cb))
    to_perm = tuple(range(0, 2 * dim, 2)) + tuple(range(1, 2 * dim, 2))
    return split, to_perm


@functools.partial(jax.jit, static_argnames=("grid_cells", "cb", "box_lo",
                                             "box_hi", "interpret",
                                             "precision"))
def p2m_cells(cell_x, cell_val, cell_mask, *, grid_cells, cb: int,
              box_lo, box_hi, interpret: bool = False,
              precision: str = "fp32") -> jax.Array:
    """Conflict-free P2M over pre-bucketed particle tiles.

    cell_x:    (n_cells, cc, dim) slot positions, flat C-order cell index.
    cell_val:  (n_cells, cc, C) slot values.
    cell_mask: (n_cells, cc) slot occupancy.
    Returns the mesh field ``tuple(cb*g for g in grid_cells) + (C,)``.

    Kernel layout: bucket operands go component-major, ``(dim, cc)`` /
    ``(C, cc)`` / ``(1, cc)`` per cell, and each grid step writes one
    ``(cb^dim, C)`` patch block — every block's last two dims are full
    array dims, as the TPU tiling rule asks.
    """
    dim = len(grid_cells)
    cc = cell_x.shape[1]
    n_ch = cell_val.shape[-1]
    shape = tuple(cb * g for g in grid_cells)
    lo = tuple(float(v) for v in box_lo)
    lengths = tuple(float(hi) - float(l) for l, hi in zip(box_lo, box_hi))
    h = tuple(L / n for L, n in zip(lengths, shape))

    offsets = _offsets(dim)
    cm = lambda a: jnp.swapaxes(a, 1, 2).reshape(
        grid_cells + (a.shape[2], cc)).astype(jnp.float32)
    gx, gv = cm(cell_x), cm(cell_val)
    gm = cm(cell_mask[..., None])

    def nbr_spec(rows, off):
        def imap(*ids):
            return tuple((ids[d] + off[d]) % grid_cells[d]
                         for d in range(dim)) + (0, 0)
        return pl.BlockSpec((1,) * dim + (rows, cc), imap)

    in_specs = ([nbr_spec(dim, off) for off in offsets]
                + [nbr_spec(n_ch, off) for off in offsets]
                + [nbr_spec(1, off) for off in offsets])
    out_specs = pl.BlockSpec((1,) * dim + (cb ** dim, n_ch),
                             lambda *ids: ids + (0, 0))
    kern = functools.partial(_p2m_kernel, offsets=offsets,
                             grid_cells=grid_cells, cb=cb, lo=lo, h=h,
                             lengths=lengths, precision=precision)
    K = len(offsets)
    out = pl.pallas_call(
        kern,
        grid=grid_cells,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=jax.ShapeDtypeStruct(grid_cells + (cb ** dim, n_ch),
                                       jnp.float32),
        interpret=interpret,
        name="m4_p2m",
    )(*([gx] * K + [gv] * K + [gm] * K))
    _, to_perm = _blocked(shape, cb)
    back = tuple(int(i) for i in np.argsort(to_perm)) + (2 * dim,)
    return out.reshape(grid_cells + (cb,) * dim + (n_ch,)
                       ).transpose(back).reshape(shape + (n_ch,))


def _m2p_kernel(*refs, offsets, grid_cells, cb, lo, h, precision="fp32"):
    dim = len(grid_cells)
    K = len(offsets)
    f_refs = refs[:K]
    x_ref, m_ref, o_ref = refs[K], refs[K + 1], refs[K + 2]
    node_cols = _node_coords(cb, dim)
    lead = (0,) * dim
    acc = jnp.zeros(o_ref.shape[dim:], jnp.float32)        # (n_ch, cc)
    zero = jnp.float32(0.0)
    for n, off in enumerate(offsets):
        # unwrapped node coordinates of this neighbor field block — the
        # index_map fetched the wrapped data, so raw distances are the
        # minimum-image ones
        cells = [pl.program_id(d) + off[d] for d in range(dim)]
        w = _weights(x_ref, m_ref, cells, node_cols, [zero] * dim, cb, lo,
                     h)                                     # (cb^dim, cc)
        acc = acc + _dot(f_refs[n][lead], w, ((1,), (0,)), precision)
    o_ref[lead] = acc


@functools.partial(jax.jit, static_argnames=("grid_cells", "cb", "box_lo",
                                             "box_hi", "interpret",
                                             "precision"))
def m2p_cells(field, cell_x, cell_mask, *, grid_cells, cb: int,
              box_lo, box_hi, interpret: bool = False,
              precision: str = "fp32") -> jax.Array:
    """Fused M2P gather over pre-bucketed particle tiles.

    field:     mesh array ``shape + (C,)`` — C may stack several physical
               fields (u and RHS in one pass).
    Returns per-slot values (n_cells, cc, C).

    Kernel layout: the field goes in as ``(C, cb^dim)`` patch blocks and
    buckets component-major (as in :func:`p2m_cells`); each grid step
    writes a ``(C, cc)`` block.
    """
    dim = len(grid_cells)
    cc = cell_x.shape[1]
    n_ch = field.shape[-1]
    shape = field.shape[:-1]
    assert shape == tuple(cb * g for g in grid_cells), (shape, grid_cells, cb)
    lo = tuple(float(v) for v in box_lo)
    lengths = tuple(float(hi) - float(l) for l, hi in zip(box_lo, box_hi))
    h = tuple(L / n for L, n in zip(lengths, shape))

    offsets = _offsets(dim)
    cm = lambda a: jnp.swapaxes(a, 1, 2).reshape(
        grid_cells + (a.shape[2], cc)).astype(jnp.float32)
    gx, gm = cm(cell_x), cm(cell_mask[..., None])
    split, to_perm = _blocked(shape, cb)
    gf = field.astype(jnp.float32).reshape(split + (n_ch,)).transpose(
        to_perm[:dim] + (2 * dim,) + to_perm[dim:]
    ).reshape(grid_cells + (n_ch, cb ** dim))

    def field_spec(off):
        def imap(*ids):
            return tuple((ids[d] + off[d]) % grid_cells[d]
                         for d in range(dim)) + (0, 0)
        return pl.BlockSpec((1,) * dim + (n_ch, cb ** dim), imap)

    tile_spec = lambda rows: pl.BlockSpec(
        (1,) * dim + (rows, cc), lambda *ids: ids + (0, 0))
    in_specs = ([field_spec(off) for off in offsets]
                + [tile_spec(dim), tile_spec(1)])
    kern = functools.partial(_m2p_kernel, offsets=offsets,
                             grid_cells=grid_cells, cb=cb, lo=lo, h=h,
                             precision=precision)
    K = len(offsets)
    out = pl.pallas_call(
        kern,
        grid=grid_cells,
        in_specs=in_specs,
        out_specs=tile_spec(n_ch),
        out_shape=jax.ShapeDtypeStruct(grid_cells + (n_ch, cc), jnp.float32),
        interpret=interpret,
        name="m4_m2p",
    )(*([gf] * K + [gx, gm]))
    n_cells = int(np.prod(grid_cells))
    return jnp.swapaxes(out.reshape(n_cells, n_ch, cc), 1, 2)
