"""Pairwise particle interaction engine — ``applyKernel_in[_sym]`` (paper
Listing 4.1, lines 50-51).

Four execution paths, all numerically identical (property-tested):

  * ``apply_kernel_verlet``      — full Verlet-list gather; one row of
    neighbors per particle. General, simple.
  * ``apply_kernel_verlet_sym``  — *symmetric* half-list evaluation: each
    pair computed once, the j-side contribution scattered back with a
    segment-sum — the TPU rendering of the paper's ghost_put(sum) symmetric
    optimization (§4.1).
  * ``apply_kernel_cells``       — cell-blocked dense tiles: for each cell,
    interact its ≤cell_cap particles against the 3^dim-neighborhood
    candidates as one dense masked tile. Streams over cells with
    ``lax.map`` so peak memory is batch-bounded.
  * ``backend="pallas"`` (via :func:`apply_pair_kernel`) — the same dense
    cell tiles evaluated by the unified Pallas cell-pair engine
    (``kernels/cell_pair``): the pair hot loop runs entirely in VMEM,
    with one shared implementation of the gather/pad/mask/scatter
    plumbing for every pairwise workload (MD, SPH, DEM, ...).

Interaction kernels are user functions ``kernel(dx, r2, wi, wj) -> value``
where ``dx = x_i - x_j`` (minimum image), matching the paper's
``DEFINE_INTERACTION`` pattern. Kernels must be *additive* (paper §2), so the
result is order-independent.

Workloads that want both backends write the physics once as a *pair body*
(the cell-pair engine protocol, DESIGN.md §2):

    body(dx, r2, ok, wi, wj) -> {name: per-pair value}

      dx(d)  -> displacement component d of x_i - x_j (callable, so Pallas
                keeps tiles 2-D per component)
      r2     -> squared pair distance
      ok     -> pair validity (cutoff + slot masks + self-exclusion)
      wi[k]  -> i-side property; scalars broadcast against the pair shape,
                vectors expose components via ``[..., d]``
      value  -> per-pair scalar (summed over j) or :class:`Radial` (the
                engine emits ``Σ_j mag · dx`` — forces, accelerations)

``apply_pair_kernel(..., backend="jnp")`` routes a body through
:func:`apply_kernel_cells` via :func:`as_jnp_kernel`; ``backend="pallas"``
routes it through ``kernels.cell_pair.apply_kernel_pallas``. The jnp path
is the oracle for the Pallas path.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from .particles import ParticleSet
from .cell_list import CellList, VerletList, neighborhood, _min_image

KernelFn = Callable[..., Any]


@dataclasses.dataclass(frozen=True)
class Radial:
    """Marker for a radially-directed per-pair value: the contribution of
    pair (i, j) is ``mag * (x_i - x_j)`` — the shape of every central
    force. Bodies return it so the engine can contract the magnitude
    against displacement components without materializing pair vectors."""

    mag: Any


def check_out_kind(name: str, kind: str, value):
    """Validate a body's returned value against its declared ``out`` kind
    (both backends call this, so a mismatched body fails loudly and
    identically instead of silently diverging). Returns the magnitude for
    radial outputs, the value itself for scalar ones."""
    if kind == "radial":
        if not isinstance(value, Radial):
            raise TypeError(
                f"pair-body output {name!r} is declared 'radial' but the "
                f"body returned a bare value; wrap it in Radial(mag)")
        return value.mag
    if isinstance(value, Radial):
        raise TypeError(
            f"pair-body output {name!r} is declared {kind!r} but the body "
            f"returned Radial; declare it 'radial' or return the array")
    return value


def cast_bf16(w):
    """bf16x operand cast: floating-point properties to bfloat16, integer
    properties (ids, kinds) untouched. Shared by both backends so the body
    sees identical operand dtypes either way."""
    return jax.tree.map(
        lambda a: a.astype(jnp.bfloat16)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, w)


def parse_precision(precision: str, out):
    """Parse a pair-engine precision mode (both backends route through
    this, so the selection grammar and its errors are identical).

    ``"fp32"`` | ``"bf16x"`` — whole-body modes (all outputs). The
    per-output form ``"bf16x:<name>[,<name>...]"`` lowers only the listed
    pair outputs to bf16 operands; the rest stay full fp32 — e.g. SPH's
    ``"bf16x:drho"`` runs the density summation mixed-precision while the
    EOS force pass keeps fp32 (its stiff pressure term is precision-
    sensitive). Returns ``(mode, selection)`` where selection is a
    frozenset of output names or None (all outputs — pure modes single-
    evaluate the body, bitwise the legacy paths)."""
    mode, _, names = precision.partition(":")
    if mode not in ("fp32", "bf16x"):
        raise ValueError(f"unknown precision {precision!r}; want 'fp32', "
                         "'bf16x', or 'bf16x:<out,...>'")
    if not names:
        return mode, None
    if mode != "bf16x":
        raise ValueError(f"precision {precision!r}: per-output selection "
                         "only applies to 'bf16x'")
    sel = frozenset(names.split(","))
    unknown = sel - set(out)
    if unknown:
        raise ValueError(
            f"precision {precision!r} selects unknown pair outputs "
            f"{sorted(unknown)}; declared outputs are {sorted(out)}")
    if sel >= set(out):
        return mode, None      # every output selected == pure bf16x
    return mode, sel


def as_jnp_kernel(body, out, r_cut: float,
                  precision: str = "fp32") -> KernelFn:
    """Adapt a pair *body* (the cell-pair engine protocol above) into a
    ``kernel(dx, r2, wi, wj)`` for the jnp paths — single-source physics.
    ``out`` maps result name -> "scalar" | "radial" (same declaration the
    Pallas engine consumes); ``r_cut`` rebuilds the engine's cutoff mask
    so the body sees identical ``ok`` semantics.

    ``precision="bf16x"`` (DESIGN.md §12): geometry (dx, r2, the ok mask)
    stays fp32, the *body* sees bf16 operands and computes per-pair values
    in bf16, and the engine's per-particle sums accumulate in fp32 with
    fp32 outputs — the classic mixed-precision contract.
    ``"bf16x:<name,...>"`` applies that contract to the listed outputs
    only (the body is evaluated under both operand precisions and each
    output keeps its selected evaluation — see :func:`parse_precision`).
    ``"fp32"`` is the default and leaves the kernel bitwise-untouched."""
    mode, sel = parse_precision(precision, out)
    rc2 = r_cut * r_cut

    def kernel(dx_arr, r2, wi, wj):
        ok = (r2 < rc2) & (r2 > 1e-12)

        def eval_all(bf16: bool):
            if bf16:
                dxa = dx_arr.astype(jnp.bfloat16)
                r2a = r2.astype(jnp.bfloat16)
                wia, wja = cast_bf16(wi), cast_bf16(wj)
            else:
                dxa, r2a, wia, wja = dx_arr, r2, wi, wj
            dx = lambda d: dxa[..., d]
            vals = body(dx, r2a, ok, wia, wja)
            res = {}
            for name, kind in sorted(out.items()):
                v = check_out_kind(name, kind, vals[name])
                if kind == "radial":
                    v = jnp.where(ok, v, 0.0)[..., None] * dxa
                else:
                    v = jnp.where(ok, v, 0.0)
                # fp32 accumulators/outputs: the downstream per-particle
                # sum runs on this cast result
                res[name] = v.astype(jnp.float32)
            return res

        if sel is None:
            return eval_all(mode == "bf16x")
        bf, fp = eval_all(True), eval_all(False)
        return {name: bf[name] if name in sel else fp[name] for name in fp}

    return kernel


def apply_pair_kernel(ps: ParticleSet, cl: CellList, body, *, out,
                      r_cut: float, prop_names=(), backend: str = "jnp",
                      interpret: bool | None = None, cell_batch: int = 256,
                      cells=None, precision: str = "fp32"):
    """Uniform front door over the cell-blocked execution paths.

    ``body`` follows the pair-body protocol (module docstring); ``out``
    maps result name -> "scalar" | "radial". ``backend="jnp"`` evaluates
    via :func:`apply_kernel_cells` (portable, the oracle);
    ``backend="pallas"`` via the unified cell-pair engine
    (``kernels/cell_pair``), with ``interpret=None`` auto-enabling
    interpret mode off-TPU. Returns {name: (cap, ...) per-particle sums}.

    ``cells`` restricts evaluation to the given *home* cell indices (int32,
    entries == n_cells are inactive sentinels); candidates are still
    gathered from the full cell array, so the sums for particles homed in
    selected cells are identical to the full evaluation — the primitive
    behind split-phase interior/boundary stepping (DESIGN.md §12).
    ``precision="bf16x"`` selects bf16 body operands with fp32
    accumulation; ``"fp32"`` (default) is bitwise the legacy path.
    """
    if backend == "jnp":
        kern = as_jnp_kernel(body, out, r_cut, precision=precision)
        return apply_kernel_cells(ps, cl, kern, r_cut=r_cut,
                                  prop_names=prop_names,
                                  cell_batch=cell_batch, cells=cells)
    if backend == "pallas":
        # deferred import: core must stay importable without kernels/
        from repro.kernels.cell_pair.cell_pair import apply_kernel_pallas
        return apply_kernel_pallas(ps, cl, body, out=out, r_cut=r_cut,
                                   prop_names=prop_names,
                                   interpret=interpret, cells=cells,
                                   precision=precision)
    raise ValueError(f"unknown backend {backend!r}; want 'jnp' or 'pallas'")


def _gather_props(props, idx, cap):
    safe = jnp.minimum(idx, cap - 1)
    return jax.tree.map(lambda a: a[safe], props)


def apply_kernel_verlet(ps: ParticleSet, vl: VerletList, cl: CellList,
                        kernel: KernelFn, prop_names=(), batch_size: int = 2048):
    """result_i = sum_j kernel(x_i - x_j, r2, w_i, w_j) over Verlet neighbors."""
    cap = ps.capacity
    xm = ps.masked_x()
    props = {k: ps.props[k] for k in prop_names}

    def per_particle(i):
        nbr = vl.nbr[i]                     # (k_max,)
        ok = nbr < cap
        xj = xm[jnp.minimum(nbr, cap - 1)]
        dx = _min_image(xm[i] - xj, cl)
        r2 = jnp.sum(dx * dx, axis=-1)
        wi = jax.tree.map(lambda a: a[i], props)
        wj = _gather_props(props, nbr, cap)
        val = kernel(dx, r2, wi, wj)        # pytree with leading dim k_max
        val = jax.tree.map(
            lambda v: jnp.sum(jnp.where(_bmask(ok, v), v, 0), axis=0), val)
        return val

    out = jax.lax.map(per_particle, jnp.arange(cap, dtype=jnp.int32),
                      batch_size=min(cap, batch_size))
    return jax.tree.map(
        lambda v: jnp.where(_bmask(ps.valid, v), v, 0), out)


def apply_kernel_verlet_sym(ps: ParticleSet, vl: VerletList, cl: CellList,
                            kernel: KernelFn, prop_names=(),
                            antisymmetric: bool = True):
    """Symmetric half-list evaluation: pairs (i, j>i) computed once; the
    reverse contribution is scattered to j (sign-flipped if antisymmetric,
    e.g. forces; plain for symmetric scalars like SPH density).

    This is the ghost_put(sum)-style path: on a distributed run the scatter
    to ghost rows is followed by ``mappings.ghost_put`` to return ghost
    contributions to their owners.
    """
    cap, k_max = vl.nbr.shape
    xm = ps.masked_x()
    props = {k: ps.props[k] for k in prop_names}
    i_idx = jnp.repeat(jnp.arange(cap, dtype=jnp.int32), k_max)
    j_idx = vl.nbr.reshape(-1)
    ok = j_idx < cap
    j_safe = jnp.minimum(j_idx, cap - 1)
    dx = _min_image(xm[i_idx] - xm[j_safe], cl)
    r2 = jnp.sum(dx * dx, axis=-1)
    wi = _gather_props(props, i_idx, cap)
    wj = _gather_props(props, j_safe, cap)
    val = kernel(dx, r2, wi, wj)
    val = jax.tree.map(lambda v: jnp.where(_bmask(ok, v), v, 0), val)
    sign = -1.0 if antisymmetric else 1.0

    def reduce(v):
        fwd = jax.ops.segment_sum(v, i_idx, num_segments=cap)
        rev = jax.ops.segment_sum(
            jnp.asarray(sign, v.dtype) * v,
            jnp.where(ok, j_idx, cap), num_segments=cap + 1)[:cap]
        return fwd + rev

    out = jax.tree.map(reduce, val)
    return jax.tree.map(lambda v: jnp.where(_bmask(ps.valid, v), v, 0), out)


def apply_kernel_cells(ps: ParticleSet, cl: CellList, kernel: KernelFn,
                       r_cut: float, prop_names=(), cell_batch: int = 256,
                       cells=None):
    """Cell-blocked dense-tile evaluation (structural twin of the unified
    Pallas cell-pair engine, kernels/cell_pair — this is its oracle path).
    For each cell: (cell_cap) x (3^dim * cell_cap) masked pair tile.
    Periodic images are resolved by shifting each neighbor cell's
    positions by its box offset (``neighborhood_shifts``), so the direct
    displacement equals the image displacement for any grid size — same
    semantics as the Pallas engine's gather. Returns per-particle sums
    (same layout as the particle set).

    ``cells`` (optional int32 array) restricts the evaluated *home* cells;
    entries ``>= n_cells`` are inactive sentinels contributing nothing.
    Candidate tiles still come from the full cell array, so restricted
    sums match the full evaluation for particles homed in selected cells.
    """
    cap = ps.capacity
    cell_cap = cl.cell_cap
    hood, shifts = neighborhood(cl)         # (n_cells, K), (n_cells, K, dim)
    n_cells, K = hood.shape
    xm = ps.masked_x()
    props = {k: ps.props[k] for k in prop_names}
    rc2 = r_cut * r_cut

    def per_cell(c):
        with jax.named_scope("candidate_gather"):
            active = c < n_cells
            c = jnp.minimum(c, n_cells - 1)
            rows = jnp.where(active, cl.cells[c], cap)      # (cell_cap,)
            cand2 = cl.cells[hood[c]]                       # (K, cell_cap)
            cand = cand2.reshape(K * cell_cap)
            xi = xm[jnp.minimum(rows, cap - 1)]             # (cc, dim)
            xj = (xm[jnp.minimum(cand2, cap - 1)]           # (Kcc, dim)
                  + shifts[c][:, None, :]).reshape(K * cell_cap, -1)
            wi = _gather_props(props, rows, cap)
            wj = _gather_props(props, cand, cap)
        with jax.named_scope("pair_kernel"):
            dx = xi[:, None, :] - xj[None, :, :]
            r2 = jnp.sum(dx * dx, axis=-1)                  # (cc, Kcc)
            pair_ok = ((rows < cap)[:, None] & (cand < cap)[None, :]
                       & (rows[:, None] != cand[None, :]) & (r2 < rc2))
            wi_b = jax.tree.map(lambda a: a[:, None], wi)
            wj_b = jax.tree.map(lambda a: a[None, :], wj)
            val = kernel(dx, r2, wi_b, wj_b)                # (cc, Kcc, ...)
            val = jax.tree.map(
                lambda v: jnp.sum(jnp.where(_bmask(pair_ok, v), v, 0),
                                  axis=1), val)
        return rows, val

    idx = (jnp.arange(n_cells, dtype=jnp.int32) if cells is None
           else jnp.asarray(cells, jnp.int32))
    rows, vals = jax.lax.map(per_cell, idx,
                             batch_size=min(idx.shape[0], cell_batch))
    rows = rows.reshape(-1)

    def scatter(v):
        flat = v.reshape((rows.shape[0],) + v.shape[2:])
        out = jnp.zeros((cap + 1,) + flat.shape[1:], flat.dtype)
        return out.at[jnp.minimum(rows, cap)].add(
            jnp.where(_bmask(rows < cap, flat), flat, 0))[:cap]

    with jax.named_scope("slot_scatter"):
        out = jax.tree.map(scatter, vals)
        return jax.tree.map(lambda v: jnp.where(_bmask(ps.valid, v), v, 0),
                            out)


def _bmask(mask: jax.Array, v: jax.Array) -> jax.Array:
    """Broadcast a leading-dims mask against v's trailing dims."""
    extra = v.ndim - mask.ndim
    return mask.reshape(mask.shape + (1,) * extra)
