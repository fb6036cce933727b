"""Jitted end-to-end M'4 interpolation ops: CellList bucketing (XLA) +
conflict-free Pallas P2M / fused M2P, mirroring the ``core/interp.py``
oracle signatures so apps can switch per config flag.

The cell grid is *aligned with the mesh*: each interpolation cell spans
``cb`` nodes per axis, so the Pallas grid over cells owns disjoint node
patches (see m4_interp.py). Pallas path is periodic-only; non-periodic
callers stay on the oracle.

Bucketing is the expensive XLA-side bookkeeping (one argsort + dense
gathers), so it is exposed: ``bucket_particles`` → ``p2m_bucketed`` /
``m2p_fused_bucketed`` lets callers interpolating several quantities at
the *same* positions (the VIC RK2 stage does P2M and M2P at x1) pay for
it once. Bucket overflow (particles beyond ``cell_cap`` in one cell) is
*detected* and surfaced — the repo-wide contract: the control plane
re-provisions capacity rather than computing silently wrong answers.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cell_list as CL
from repro.core.particles import ParticleSet
from repro.kernels.m4_interp.m4_interp import m2p_cells, p2m_cells

DEFAULT_CB = 4


def default_cell_cap(cb: int, dim: int) -> int:
    """Default bucket capacity: 2× the one-particle-per-node density that
    remeshed VIC maintains. The single source for re-provisioning callers."""
    return 2 * cb ** dim


class InterpBuckets(NamedTuple):
    """Dense (n_cells, cc, ·) slot tiles from one bucketing pass."""
    cell_x: jax.Array      # (n_cells, cc, dim) slot positions
    cell_mask: jax.Array   # (n_cells, cc) slot occupancy
    safe: jax.Array        # (n_cells, cc) clamped slot→particle index
    overflow: jax.Array    # () total dropped particles (cell_cap exceeded)


def _auto_interpret(interpret):
    if interpret is None:
        return jax.devices()[0].platform != "tpu"
    return interpret


def _check_layout(shape, periodic, cb):
    if cb < 2:
        raise ValueError(
            f"cb={cb}: the 3^dim neighbor-bucket gather only covers the M'4 "
            "support (2h) for cb >= 2")
    if not all(periodic):
        raise NotImplementedError(
            "m4_interp Pallas path is periodic-only; use core.interp for "
            f"clamped boundaries (periodic={periodic})")
    if any(n % cb for n in shape):
        raise ValueError(f"mesh shape {shape} not divisible by cb={cb}")
    return tuple(n // cb for n in shape)


@partial(jax.jit, static_argnames=("shape", "box_lo", "box_hi", "periodic",
                                   "cb", "cell_cap"))
@jax.named_scope("m4_bucketing")
def bucket_particles(x, valid, *, shape, box_lo, box_hi, periodic,
                     cb: int = DEFAULT_CB,
                     cell_cap: int = 0) -> InterpBuckets:
    """Bin particles into mesh-aligned interpolation cells via CellList.

    ``cell_cap`` defaults to ``2·cb^dim`` (double the one-per-node density
    remeshed VIC maintains); arbitrary clouds must size it explicitly.
    Overflow > 0 means that many particles were dropped — re-provision.
    """
    dim = len(shape)
    grid_cells = _check_layout(shape, periodic, cb)
    cell_cap = cell_cap or default_cell_cap(cb, dim)
    ps = ParticleSet(x=jnp.where(valid[:, None], x,
                                 jnp.full_like(x, ParticleSet.FILL)),
                     props={}, valid=valid)
    cl = CL.build_cell_list(ps, box_lo=tuple(box_lo), box_hi=tuple(box_hi),
                            grid_shape=grid_cells, periodic=tuple(periodic),
                            cell_cap=cell_cap)
    cap = ps.capacity
    n_cells = int(np.prod(grid_cells))
    rows = cl.cells[:n_cells]                    # (n_cells, cc)
    safe = jnp.minimum(rows, cap - 1)
    # total dropped particles (CellList.overflow is only the worst cell's
    # excess; sum the per-cell excess so callers report a true count)
    dropped = jnp.sum(jnp.maximum(cl.counts[:n_cells] - cell_cap, 0))
    return InterpBuckets(cell_x=ps.x[safe], cell_mask=rows < cap, safe=safe,
                         overflow=dropped.astype(jnp.int32))


@partial(jax.jit, static_argnames=("shape", "box_lo", "box_hi", "periodic",
                                   "cb", "interpret", "precision"))
def p2m_bucketed(buckets: InterpBuckets, value, *, shape, box_lo, box_hi,
                 periodic, cb: int = DEFAULT_CB, interpret=None,
                 precision: str = "fp32"):
    """P2M from an existing bucketing. ``value``: (N,) or (N, C) indexed by
    the particle slots the buckets were built from."""
    interpret = _auto_interpret(interpret)
    grid_cells = _check_layout(shape, periodic, cb)
    vec = value.ndim == 2
    val2 = value if vec else value[:, None]
    with jax.named_scope("m4_p2m"):
        cell_val = val2[buckets.safe]
        out = p2m_cells(buckets.cell_x, cell_val, buckets.cell_mask,
                        grid_cells=grid_cells, cb=cb, box_lo=tuple(box_lo),
                        box_hi=tuple(box_hi), interpret=interpret,
                        precision=precision)
    out = out.astype(value.dtype)
    return out if vec else out[..., 0]


@partial(jax.jit, static_argnames=("shape", "box_lo", "box_hi", "periodic",
                                   "cb", "interpret", "precision"))
def m2p_fused_bucketed(buckets: InterpBuckets, fields, valid, *, shape,
                       box_lo, box_hi, periodic, cb: int = DEFAULT_CB,
                       interpret=None, precision: str = "fp32"):
    """Fused M2P from an existing bucketing: interpolate several mesh
    fields (each ``shape`` or ``shape + (C,)``) in ONE kernel pass — the
    weight tile is computed once for all stacked channels. Returns a tuple
    matching ``fields``."""
    interpret = _auto_interpret(interpret)
    grid_cells = _check_layout(shape, periodic, cb)
    dim = len(shape)
    fields = tuple(fields)
    chans = [1 if f.ndim == dim else f.shape[-1] for f in fields]
    with jax.named_scope("m4_m2p"):
        stacked = jnp.concatenate(
            [f[..., None] if f.ndim == dim else f for f in fields], axis=-1)
        tiles = m2p_cells(stacked, buckets.cell_x, buckets.cell_mask,
                          grid_cells=grid_cells, cb=cb, box_lo=tuple(box_lo),
                          box_hi=tuple(box_hi), interpret=interpret,
                          precision=precision)
    cap = valid.shape[0]
    with jax.named_scope("m4_unbucket"):
        flat_rows = buckets.safe.reshape(-1)
        # ``safe`` clamps the sentinel into range, so scatter with the
        # mask-selected values; each valid particle occupies exactly one
        # slot.
        flat_vals = jnp.where(buckets.cell_mask.reshape(-1)[:, None],
                              tiles.reshape(-1, tiles.shape[-1]), 0.0)
        per_p = jnp.zeros((cap, tiles.shape[-1]), jnp.float32
                          ).at[flat_rows].add(flat_vals)
        per_p = jnp.where(valid[:, None], per_p, 0.0)
    out, c0 = [], 0
    for f, c in zip(fields, chans):
        piece = per_p[:, c0:c0 + c].astype(f.dtype)
        out.append(piece[:, 0] if f.ndim == dim else piece)
        c0 += c
    return tuple(out)


# --------------------------------------------------------------------------
# Local-block legs (slab-distributed P2M/M2P, DESIGN.md §10)
# --------------------------------------------------------------------------
# A slab shard deposits into / gathers from a block of ``block_rows`` global
# rows starting at traced ``row0`` (owned rows ± halo) instead of the global
# mesh. The Pallas kernels are torus kernels, so the block is embedded in a
# local torus: rows padded up to a multiple of ``cb``, positions re-origined
# at the block start. Particles whose M'4 support leaves the block are
# masked to the trash bucket and counted (same drop-and-surface contract as
# ``core.interp.p2m_block`` — the oracle these are tested against); for
# kept particles the torus wrap never engages, so results match the oracle.

def _block_frame(x, valid, row0, block_rows, shape, box_lo, box_hi,
                 periodic, cb):
    """(x_local, ok, padded_rows, local box) for a block embedded in a
    cb-aligned local torus."""
    from repro.core import interp as IP
    lo, h = IP._node_spacing(shape, box_lo, box_hi, periodic)
    base, frac = IP._block_base_frac(x, row0, block_rows, shape, box_lo,
                                     box_hi, periodic)
    ok = valid & IP._block_ok(base[:, 0], block_rows)
    rows_k = -(-block_rows // cb) * cb
    # local coordinate rebuilt from the folded relative row + exact frac —
    # the kernel re-derives the same (base, frac) the oracle committed to
    x0_rel = (base[:, 0].astype(x.dtype) + frac[:, 0]) \
        * jnp.asarray(h[0], x.dtype)
    x_loc = x.at[:, 0].set(x0_rel)
    x_loc = jnp.where(ok[:, None], x_loc,
                      jnp.full_like(x_loc, ParticleSet.FILL))
    local_lo = (0.0,) + tuple(float(v) for v in np.asarray(box_lo)[1:])
    local_hi = (float(rows_k * h[0]),) + tuple(
        float(v) for v in np.asarray(box_hi)[1:])
    return x_loc, ok, rows_k, local_lo, local_hi


def p2m_block(x, value, valid, row0, *, block_rows: int, shape, box_lo,
              box_hi, periodic, cb: int = DEFAULT_CB, cell_cap: int = 0,
              interpret=None, precision: str = "fp32"):
    """Pallas P2M onto a local slab block — drop-in for
    ``core.interp.p2m_block`` (periodic global axes only). Returns
    ``(block, overflow)``; overflow sums dropped-support particles and
    bucket-capacity drops."""
    x_loc, ok, rows_k, lo_l, hi_l = _block_frame(
        x, valid, row0, block_rows, shape, box_lo, box_hi, periodic, cb)
    kw = dict(shape=(rows_k,) + tuple(shape[1:]), box_lo=lo_l, box_hi=hi_l,
              periodic=tuple(periodic), cb=cb)
    b = bucket_particles(x_loc, ok, cell_cap=cell_cap, **kw)
    vec = value.ndim == 2
    vmask = ok[:, None] if vec else ok
    out = p2m_bucketed(b, jnp.where(vmask, value, 0), interpret=interpret,
                       precision=precision, **kw)
    dropped = jnp.sum(valid & ~ok).astype(jnp.int32)
    return out[:block_rows], b.overflow + dropped


def m2p_fused_block(blocks, x, valid, row0, *, shape, box_lo, box_hi,
                    periodic, cb: int = DEFAULT_CB, cell_cap: int = 0,
                    interpret=None, precision: str = "fp32"):
    """Fused Pallas M2P from local slab blocks (each ``(block_rows, ...)``,
    all the same rows) — the block counterpart of :func:`m2p_fused`.
    Returns ``(tuple(values), overflow)``; dropped particles read 0."""
    blocks = tuple(blocks)
    block_rows = blocks[0].shape[0]
    x_loc, ok, rows_k, lo_l, hi_l = _block_frame(
        x, valid, row0, block_rows, shape, box_lo, box_hi, periodic, cb)
    kw = dict(shape=(rows_k,) + tuple(shape[1:]), box_lo=lo_l, box_hi=hi_l,
              periodic=tuple(periodic), cb=cb)
    pad = [(0, rows_k - block_rows)] + [(0, 0)]
    fields = tuple(jnp.pad(f, pad + [(0, 0)] * (f.ndim - 2)) for f in blocks)
    b = bucket_particles(x_loc, ok, cell_cap=cell_cap, **kw)
    out = m2p_fused_bucketed(b, fields, ok, interpret=interpret,
                             precision=precision, **kw)
    dropped = jnp.sum(valid & ~ok).astype(jnp.int32)
    return out, b.overflow + dropped


def p2m(x, value, valid, *, shape, box_lo, box_hi, periodic,
        cb: int = DEFAULT_CB, cell_cap: int = 0, interpret=None,
        return_overflow: bool = False, precision: str = "fp32"):
    """Pallas P2M, drop-in for ``core.interp.p2m`` (periodic axes only).
    With ``return_overflow`` returns (field, dropped-particle count)."""
    kw = dict(shape=shape, box_lo=box_lo, box_hi=box_hi, periodic=periodic,
              cb=cb)
    b = bucket_particles(x, valid, cell_cap=cell_cap, **kw)
    out = p2m_bucketed(b, value, interpret=interpret, precision=precision,
                       **kw)
    return (out, b.overflow) if return_overflow else out


def m2p_fused(fields, x, valid, *, shape, box_lo, box_hi, periodic,
              cb: int = DEFAULT_CB, cell_cap: int = 0, interpret=None,
              return_overflow: bool = False, precision: str = "fp32"):
    """Fused Pallas M2P (bucket + gather in one call); see
    ``m2p_fused_bucketed``."""
    kw = dict(shape=shape, box_lo=box_lo, box_hi=box_hi, periodic=periodic,
              cb=cb)
    b = bucket_particles(x, valid, cell_cap=cell_cap, **kw)
    out = m2p_fused_bucketed(b, fields, valid, interpret=interpret,
                             precision=precision, **kw)
    return (out, b.overflow) if return_overflow else out


def m2p(field, x, valid, *, shape, box_lo, box_hi, periodic,
        cb: int = DEFAULT_CB, cell_cap: int = 0, interpret=None,
        return_overflow: bool = False):
    """Pallas M2P, drop-in for ``core.interp.m2p`` (periodic axes only)."""
    res = m2p_fused((field,), x, valid, shape=shape, box_lo=box_lo,
                    box_hi=box_hi, periodic=periodic, cb=cb,
                    cell_cap=cell_cap, interpret=interpret,
                    return_overflow=return_overflow)
    if return_overflow:
        (out,), ovf = res
        return out, ovf
    return res[0]
