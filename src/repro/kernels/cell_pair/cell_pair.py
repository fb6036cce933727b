"""Unified Pallas cell-pair interaction engine (paper §2/§4.1, DESIGN.md §2).

One implementation of the cell-blocked pair hot loop serves every pairwise
workload — MD, SPH, DEM, and whatever comes next — the ``applyKernel_in``
one-engine-many-clients argument of the paper (and of FDPS). The XLA side
pre-gathers dense per-cell candidate tiles, applying the per-neighbor-cell
periodic box shift so the kernel's *direct* displacement equals the minimum
image for any grid size; the Pallas kernel evaluates a user-supplied
~30-line *pair body* over the (cells_per_block, cell_cap, K·cell_cap)
masked tile entirely in VMEM; per-slot sums are scattered back to
particles. All pad / BlockSpec / mask / gather / scatter plumbing lives
here and only here.

Body protocol (shared with ``core.interactions.as_jnp_kernel``):

    body(dx, r2, ok, wi, wj) -> {name: value}

      dx(d)  -> displacement component d (x_i - x_j), pair-broadcast shape
      r2     -> squared distance over the pair tile
      ok     -> pair validity: slot masks & r2 < r_cut² & r2 > 0
      wi[k]  -> i-side property; scalar (Cb, cc, 1) or vector
                (Cb, cc, 1, dim) — index ``[..., d]`` for components
      wj[k]  -> j-side property; scalar (Cb, 1, Kcc) / vector
                (Cb, 1, Kcc, dim)
      value  -> per-pair scalar array (engine sums over j) or
                ``interactions.Radial(mag)`` (engine emits ``Σ_j mag·dx``)

Operands reach the kernel component-major — one (C, cc) / (C, K·cc) plane
per coordinate or property component — so the TPU tiling rule holds for
any cells_per_block that is a multiple of 8, and the kernel never slices
along the lane axis; displacements are unrolled per component and radial
outputs are contracted component-wise. Input VMEM per grid step is
(Cb·cc + Cb·K·cc)·(dim + 1 + per-prop widths)·4 bytes — for the MD
defaults (Cb=8, cc=48, K=27) about 170 KB, double-buffered; the
(Cb, cc, K·cc) pair tiles the body builds (~2 MB each at MD widths) are
the larger share. The pure-jnp oracle is
``core.interactions.apply_pair_kernel(..., backend="jnp")``, which routes
the same body through ``apply_kernel_cells`` — which is why this package
carries no separate ref.py.

Caveat: like the dense jnp cells path, the 3^dim candidate pre-gather
duplicates positions K-fold in HBM; size ``cell_cap`` to the workload.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.cell_list import CellList, neighborhood
from repro.core.interactions import (Radial, _bmask, cast_bf16,
                                     check_out_kind, parse_precision)
from repro.core.particles import ParticleSet


class CellTiles(NamedTuple):
    """Dense per-cell tiles: the engine's XLA-side pre-gather product."""

    rows: jax.Array       # (n_cells, cc) int32 particle index per slot
    cell_x: jax.Array     # (n_cells, cc, dim) home-cell positions
    nbr_x: jax.Array      # (n_cells, K*cc, dim) candidates, shift-applied
    cell_mask: jax.Array  # (n_cells, cc) bool
    nbr_mask: jax.Array   # (n_cells, K*cc) bool
    props_i: Dict[str, jax.Array]
    props_j: Dict[str, jax.Array]


@jax.named_scope("candidate_gather")
def gather_cell_tiles(ps: ParticleSet, cl: CellList, prop_names=(),
                      cells=None) -> CellTiles:
    """XLA-side pre-gather: dense per-cell tiles from a CellList. Periodic
    neighbor cells' positions are shifted by the box offset of the image
    they were reached through (``neighborhood_shifts``), so the kernel's
    direct displacement equals the periodic image displacement — exact for
    any grid size, including axes with fewer than 3 cells.

    ``cells`` (optional int32 array) restricts the gathered *home* cells;
    entries ``>= n_cells`` are inactive sentinels (their row slots come out
    masked). Candidates are still indexed from the full cell array, so
    restricted tiles equal the corresponding full tiles."""
    cap = ps.capacity
    xm = ps.masked_x()
    hood, shifts = neighborhood(cl)         # (n_cells, K), (n_cells, K, dim)
    n_cells, K = hood.shape
    cc = cl.cell_cap
    if cells is None:
        rows = cl.cells[:n_cells]                   # (n_cells, cc)
    else:
        sel = jnp.asarray(cells, jnp.int32)
        active = sel < n_cells
        safe_sel = jnp.minimum(sel, n_cells - 1)
        rows = jnp.where(active[:, None], cl.cells[safe_sel], cap)
        hood = hood[safe_sel]
        shifts = shifts[safe_sel]
        n_cells = sel.shape[0]
    cand = cl.cells[hood].reshape(n_cells, K * cc)  # (n_cells, K*cc)
    safe_r = jnp.minimum(rows, cap - 1)
    safe_c = jnp.minimum(cand, cap - 1)
    nbr_x = (xm[safe_c].reshape(n_cells, K, cc, ps.dim)
             + shifts[:, :, None, :]).reshape(n_cells, K * cc, ps.dim)
    return CellTiles(
        rows=rows, cell_x=xm[safe_r], nbr_x=nbr_x,
        cell_mask=rows < cap, nbr_mask=cand < cap,
        props_i={k: ps.props[k][safe_r] for k in prop_names},
        props_j={k: ps.props[k][safe_c] for k in prop_names})


class _Planes:
    """A vector property inside the kernel: one ``(Cb, cc, 1)`` /
    ``(Cb, 1, Kcc)`` broadcast plane per component, indexed ``w[..., d]``
    like the ``(..., dim)`` arrays of the jnp path (pair-body protocol)."""

    def __init__(self, planes):
        self.planes = planes

    def __getitem__(self, idx):
        return self.planes[idx[-1] if isinstance(idx, tuple) else idx]


def _planes(a):
    """(C, n[, w]) slot array -> (w, C, n): one (C, n) plane per
    component, so the kernel indexes components on the leading axis and
    never slices along the lane axis."""
    a = a[..., None] if a.ndim == 2 else a
    return jnp.moveaxis(a, -1, 0)


def _pair_kernel(*refs, body, prop_kinds, out_spec, dim: int, rc2: float,
                 precision: str = "fp32"):
    """Generic tile kernel: unpack refs, build the pair mask, run the body,
    reduce each output over the candidate axis. Every operand arrives as
    component planes (``_planes``), ``(Cb, cc)`` / ``(Cb, Kcc)`` blocks
    broadcast to ``(Cb, cc, 1)`` / ``(Cb, 1, Kcc)``, so pair tiles are
    ``(Cb, cc, Kcc)`` and sums reduce over lanes. ``precision="bf16x"``:
    geometry (dx, r2, ok) stays fp32, the body sees bf16 operands (halved
    VPU operand traffic), and the candidate-axis reduction accumulates in
    fp32 (``jnp.sum(..., dtype=float32)``) with fp32 outputs.
    ``"bf16x:<name,...>"`` lowers only the listed outputs — the body runs
    once per operand precision in use and each output reduces from its
    selected evaluation."""
    mode, sel = parse_precision(precision, dict(out_spec))
    it = iter(refs)
    bi = lambda ref, c: ref[c][:, :, None]     # (Cb, cc) -> (Cb, cc, 1)
    bj = lambda ref, c: ref[c][:, None, :]     # (Cb, Kcc) -> (Cb, 1, Kcc)
    xi_ref, xj_ref, mi_ref, mj_ref = next(it), next(it), next(it), next(it)
    xi = [bi(xi_ref, d) for d in range(dim)]
    xj = [bj(xj_ref, d) for d in range(dim)]
    mi, mj = bi(mi_ref, 0), bj(mj_ref, 0)      # float slot masks
    wi, wj = {}, {}
    for k, vector in prop_kinds:
        ri, rj = next(it), next(it)
        if vector:
            wi[k] = [bi(ri, c) for c in range(ri.shape[0])]
            wj[k] = [bj(rj, c) for c in range(rj.shape[0])]
        else:
            wi[k], wj[k] = bi(ri, 0), bj(rj, 0)
    out_refs = list(it)

    def dx(d):
        return xi[d] - xj[d]

    r2 = dx(0) * dx(0)
    for d in range(1, dim):
        dd = dx(d)
        r2 = r2 + dd * dd
    ok = (mi * mj > 0.5) & (r2 < rc2) & (r2 > 1e-12)

    def wrap(w, cast):
        return {k: _Planes([cast(p) for p in v]) if isinstance(v, list)
                else cast(v) for k, v in w.items()}

    def eval_body(bf16: bool):
        """(dx_fn, body values) under one operand precision."""
        if bf16:
            dxb = lambda d: dx(d).astype(jnp.bfloat16)
            return dxb, body(dxb, r2.astype(jnp.bfloat16), ok,
                             wrap(wi, cast_bf16), wrap(wj, cast_bf16))
        return dx, body(dx, r2, ok, wrap(wi, lambda a: a),
                        wrap(wj, lambda a: a))

    use_bf16 = {name: mode == "bf16x" and (sel is None or name in sel)
                for name, _ in out_spec}
    evals = {}
    for name, _ in out_spec:
        if use_bf16[name] not in evals:
            evals[use_bf16[name]] = eval_body(use_bf16[name])
    for (name, kind), oref in zip(out_spec, out_refs):
        dx_k, vals = evals[use_bf16[name]]
        zero = jnp.bfloat16(0) if use_bf16[name] else 0.0
        v = check_out_kind(name, kind, vals[name])
        if kind == "radial":
            mag = jnp.where(ok, v, zero)
            for d in range(dim):
                oref[d] = jnp.sum(mag * dx_k(d), axis=2, dtype=jnp.float32)
        else:
            oref[0] = jnp.sum(jnp.where(ok, v, zero), axis=2,
                              dtype=jnp.float32)


@jax.named_scope("pair_kernel")
def cell_pair_pallas(cell_x, nbr_x, cell_mask, nbr_mask, props_i=None,
                     props_j=None, *, body, out, r_cut: float,
                     cells_per_block: int = 8, interpret: bool = False,
                     precision: str = "fp32"):
    """Tile-level engine entry: relayout to component planes, pad to a
    cells_per_block multiple, build BlockSpecs, run the pair kernel,
    unpad.

    cell_x: (C, cc, dim); nbr_x: (C, Kcc, dim); masks (C, cc)/(C, Kcc);
    props_i/props_j: {name: (C, cc[, dim]) / (C, Kcc[, dim])}. ``out`` maps
    name -> "scalar" | "radial". Returns {name: (C, cc[, dim]) per-slot
    sums}. Self-pairs are excluded by the r² > 0 guard (a particle is its
    own neighborhood candidate at r = 0). jit at the call site.

    Layout: every operand is passed as component planes (``_planes``,
    masks as float32), so each block is ``(w, Cb, cc)`` or
    ``(w, Cb, Kcc)``: the slot axis is a full array dim and the TPU tiling
    rule only asks ``cells_per_block`` to be a multiple of 8; the kernel
    never slices along the lane axis."""
    props_i = dict(props_i or {})
    props_j = dict(props_j or {})
    C0, cc, dim = cell_x.shape
    names = tuple(sorted(props_i))
    f32 = lambda m: m.astype(jnp.float32)
    args = [_planes(cell_x), _planes(nbr_x), _planes(f32(cell_mask)),
            _planes(f32(nbr_mask))]
    for k in names:
        args += [_planes(props_i[k]), _planes(props_j[k])]
    pad = (-C0) % cells_per_block
    if pad:
        args = [jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in args]
    C = C0 + pad
    grid = (C // cells_per_block,)
    bs = lambda shape: pl.BlockSpec((shape[0], cells_per_block, shape[2]),
                                    lambda i: (0, i, 0))
    out_spec = tuple(sorted(out.items()))
    out_shapes = [jax.ShapeDtypeStruct(
        (dim if kind == "radial" else 1, C, cc), jnp.float32)
        for _, kind in out_spec]
    parse_precision(precision, out)   # validate eagerly, shared grammar
    kern = functools.partial(
        _pair_kernel, body=body,
        prop_kinds=tuple((k, props_i[k].ndim == 3) for k in names),
        out_spec=out_spec, dim=dim, rc2=r_cut * r_cut, precision=precision)
    res = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[bs(a.shape) for a in args],
        out_specs=[bs(s.shape) for s in out_shapes],
        out_shape=out_shapes,
        interpret=interpret,
        name="cell_pair",
    )(*args)
    return {name: (jnp.moveaxis(r[:, :C0], 0, -1) if kind == "radial"
                   else r[0, :C0])
            for (name, kind), r in zip(out_spec, res)}


def scatter_slots(rows: jax.Array, val: jax.Array, cap: int) -> jax.Array:
    """Slot→particle scatter-back: (n_cells, cc, ...) per-slot sums into a
    (cap, ...) per-particle array (sentinel rows land on the dropped
    cap-th slot)."""
    flat_rows = rows.reshape(-1)
    flat = val.reshape((flat_rows.shape[0],) + val.shape[2:])
    out = jnp.zeros((cap + 1,) + flat.shape[1:], flat.dtype)
    return out.at[jnp.minimum(flat_rows, cap)].add(flat)[:cap]


def apply_kernel_pallas(ps: ParticleSet, cl: CellList, body, *, out,
                        r_cut: float, prop_names=(),
                        interpret: bool | None = None, cells=None,
                        precision: str = "fp32"):
    """End-to-end Pallas path: gather → pair kernel → scatter. The fourth
    execution path of ``core.interactions`` (use
    ``apply_pair_kernel(..., backend="pallas")`` for the uniform front
    door). ``interpret=None`` auto-enables interpret mode off-TPU.
    ``cells`` / ``precision`` as in ``apply_pair_kernel``."""
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    t = gather_cell_tiles(ps, cl, prop_names, cells=cells)
    res = cell_pair_pallas(t.cell_x, t.nbr_x, t.cell_mask, t.nbr_mask,
                           t.props_i, t.props_j, body=body, out=out,
                           r_cut=r_cut, interpret=interpret,
                           precision=precision)
    cap = ps.capacity
    with jax.named_scope("slot_scatter"):
        return {name: jnp.where(_bmask(ps.valid, s), s, 0)
                for name, s in ((n, scatter_slots(t.rows, v, cap))
                                for n, v in res.items())}
