"""Mappings — OpenFPM's communication-only abstractions (paper §3.4).

OpenFPM separates computation from communication through three mappings:
``map()`` (migrate particles to their owners), ``ghost_get()`` (populate
halos), ``ghost_put()`` (return ghost contributions with sum/max/merge).
On MPI these are non-blocking point-to-point schedules (NBX for the global
map). On a TPU torus, the native primitives are dense collectives
(DESIGN.md §2):

  * ``map()``       →  bucketed ``jax.lax.all_to_all`` with fixed-capacity
                       per-destination buckets (the dense replacement for
                       dynamic sparse data exchange). Overflow is counted
                       and surfaced, not silently dropped on the floor —
                       the control plane re-provisions bucket capacity.
  * ``ghost_get()`` →  ``jax.lax.ppermute`` ±1 shifts along the mesh axis
                       (collective-permute is the native ICI neighbor op).
  * ``ghost_put()`` →  reverse ppermute + masked scatter-reduce
                       (sum / max / min merge ops).

The device-level domain decomposition is an *adaptive slab* decomposition
along one space axis: device d owns the slab ``bounds[d] <= x_axis <
bounds[d+1]``. ``bounds`` is a traced array, so the dynamic load balancer
(core/dlb.py) can move slab boundaries *inside* jit — re-decomposition
without recompilation. The full sub-sub-domain/graph machinery
(core/decomposition.py) provides the host-side cost model that chooses the
bounds; within a device the cell structures handle locality.

All functions here are written to run **inside** ``runtime.shard_map``
(the version-portable shim, core/runtime.py) over a 1-D mesh axis; the
``make_*`` wrappers construct the shard_mapped jitted callables over
globally sharded ParticleSets. Collectives are taken from ``runtime``
(DESIGN.md §2a), never from ``jax.lax`` directly.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, PartitionSpec as P

from . import runtime as RT
from .particles import ParticleSet

# --------------------------------------------------------------------------
# Local packing helper: dense per-destination buckets
# --------------------------------------------------------------------------

def bucket_pack(dest: jax.Array, payload, ndev: int, bucket_cap: int):
    """Pack rows of ``payload`` (pytree, leading dim N) into dense buckets
    (ndev, bucket_cap, ...) by destination. dest >= ndev means 'discard'.
    Returns (buckets_pytree, slot_valid (ndev, bucket_cap) bool, overflow,
    fill): ``fill`` is the fullest bucket's count, ``overflow`` its excess
    over ``bucket_cap``."""
    n = dest.shape[0]
    dest = jnp.minimum(dest, ndev)  # clamp discards to the trash bucket
    order = jnp.argsort(dest, stable=True).astype(jnp.int32)
    sorted_dest = dest[order]
    start = jnp.searchsorted(sorted_dest, sorted_dest, side="left")
    rank = jnp.arange(n, dtype=jnp.int32) - start.astype(jnp.int32)
    row = sorted_dest
    col = rank
    in_range = (row < ndev) & (col < bucket_cap)

    def scat(a):
        buf = jnp.zeros((ndev, bucket_cap) + a.shape[1:], a.dtype)
        src = a[order]
        return buf.at[jnp.where(in_range, row, ndev),
                      jnp.minimum(col, bucket_cap - 1)].set(
                          src, mode="drop")

    buckets = jax.tree.map(scat, payload)
    slot_valid = jnp.zeros((ndev, bucket_cap), bool).at[
        jnp.where(in_range, row, ndev), jnp.minimum(col, bucket_cap - 1)
    ].set(row < ndev, mode="drop")
    counts = jnp.bincount(dest, length=ndev + 1)[:ndev]
    fill = jnp.max(counts)
    overflow = jnp.maximum(fill - bucket_cap, 0)
    return buckets, slot_valid, overflow, fill


# --------------------------------------------------------------------------
# map(): particle migration (local mapping; the global map is the same code —
# NBX's dynamic sparsity is subsumed by the dense bucket exchange)
# --------------------------------------------------------------------------

def owner_of(x_axis: jax.Array, bounds: jax.Array) -> jax.Array:
    """Device owning coordinate values, given slab ``bounds`` (ndev+1,)."""
    return jnp.clip(jnp.searchsorted(bounds, x_axis, side="right") - 1,
                    0, bounds.shape[0] - 2).astype(jnp.int32)


@jax.named_scope("map")
def map_particles_local(ps: ParticleSet, bounds: jax.Array, axis_name: str,
                        bucket_cap: int, slab_axis: int = 0):
    """The ``map()`` mapping, run inside shard_map. Returns (new_ps,
    overflow, fill).

    overflow = max(bucket overflow, slot overflow), reduced over the mesh:
    nonzero means capacities must be re-provisioned (control-plane
    responsibility; state remains consistent for retained particles).
    fill is this device's fullest outgoing bucket (not reduced)."""
    ndev = RT.axis_size(axis_name)
    me = RT.axis_index(axis_name)
    dest = owner_of(ps.x[:, slab_axis], bounds)
    dest = jnp.where(ps.valid, dest, ndev)
    stay = ps.valid & (dest == me)
    leaving_dest = jnp.where(ps.valid & ~stay, dest, ndev)

    payload = {"x": ps.x, "props": ps.props}
    buckets, slot_valid, ovf, fill = bucket_pack(leaving_dest, payload, ndev,
                                                 bucket_cap)

    def a2a(a):
        return RT.all_to_all(a, axis_name, split_axis=0, concat_axis=0,
                             tiled=False)

    recv = jax.tree.map(a2a, buckets)
    recv_valid = a2a(slot_valid)
    # all_to_all keeps the leading (ndev, bucket_cap, ...) shape; flatten.
    flat = jax.tree.map(lambda a: a.reshape((ndev * bucket_cap,) + a.shape[2:]),
                        recv)
    incoming = ParticleSet(
        x=flat["x"], props=flat["props"],
        valid=recv_valid.reshape(ndev * bucket_cap))
    kept = ps.where(stay)
    merged, add_ovf = kept.add_count(incoming)
    # overflow must be reduced across devices so every shard agrees
    total_ovf = RT.pmax(jnp.maximum(ovf, add_ovf), axis_name)
    return merged, total_ovf, fill


# --------------------------------------------------------------------------
# ghost_get(): populate halo layers from neighbor slabs
# --------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GhostLayer:
    """Halo particles received from slab neighbors.

    Layout: (2*K, ghost_cap, ...) for a K-hop exchange — rows ``0..K-1``
    came from the left neighbors at hop distances ``1..K`` (so they sit near
    our lower boundary), rows ``K..2K-1`` from the right neighbors at hops
    ``1..K``. The classic single-hop exchange is K=1: ``[from_left,
    from_right]``. ``src_slot`` is the slot index in the *source* device's
    ParticleSet, the provenance that ghost_put uses to route contributions
    home (DESIGN.md §13)."""

    x: jax.Array            # (2K, ghost_cap, dim)
    props: Dict[str, Any]   # (2K, ghost_cap, ...)
    valid: jax.Array        # (2K, ghost_cap)
    src_slot: jax.Array     # (2K, ghost_cap) int32

    @property
    def ghost_cap(self) -> int:
        return self.x.shape[1]

    @property
    def n_hops(self) -> int:
        return self.x.shape[0] // 2

    def as_particles(self) -> ParticleSet:
        g = self.ghost_cap
        rows = self.x.shape[0] * g
        return ParticleSet(
            x=self.x.reshape(rows, -1),
            props=jax.tree.map(
                lambda a: a.reshape((rows,) + a.shape[2:]), self.props),
            valid=self.valid.reshape(rows))


def _pack_side(ps: ParticleSet, sel: jax.Array, ghost_cap: int):
    """Pack selected particles (mask sel) into a dense (ghost_cap, ...) buffer,
    recording source slots. Returns (x, props, valid, src_slot, overflow,
    n_sel): ``n_sel`` selected, ``overflow`` of them beyond ghost_cap."""
    cap = ps.capacity
    rank = jnp.cumsum(sel) - 1
    slot = jnp.where(sel & (rank < ghost_cap), rank, ghost_cap)

    def scat(a):
        buf = jnp.zeros((ghost_cap,) + a.shape[1:], a.dtype)
        return buf.at[slot].set(a, mode="drop")

    x = scat(ps.x)
    props = jax.tree.map(scat, ps.props)
    valid = jnp.zeros((ghost_cap,), bool).at[slot].set(True, mode="drop")
    src = jnp.full((ghost_cap,), cap, jnp.int32).at[slot].set(
        jnp.arange(cap, dtype=jnp.int32), mode="drop")
    n_sel = jnp.sum(sel)
    overflow = jnp.maximum(n_sel - ghost_cap, 0)
    return x, props, valid, src, overflow, n_sel


@jax.named_scope("ghost_get")
def ghost_get_local(ps: ParticleSet, bounds: jax.Array, r_ghost: float,
                    axis_name: str, ghost_cap: int, *, periodic: bool,
                    box_len: float, slab_axis: int = 0,
                    prop_names: Tuple[str, ...] | None = None,
                    n_hops: int = 1) -> Tuple[GhostLayer, jax.Array]:
    """The ``ghost_get`` mapping (inside shard_map): send particles within
    ``r_ghost`` of each slab face to the respective neighbor. Positions of
    ghosts crossing the periodic seam are shifted by ±L, so downstream
    kernels never need minimum-image logic for ghosts.

    ``prop_names`` mirrors OpenFPM's property-subset ghost_get
    (``ghost_get<prop...>()``): only the listed properties are
    communicated (all, if None).

    ``n_hops`` is the multi-hop generalization (DESIGN.md §13): hop ``h``
    ships, via the ±h ring permutation, every particle the h-distant slab
    needs for its ghost window ``[lo - r_ghost, lo)`` / ``[hi, hi +
    r_ghost)``. Because the hop-h contribution is exactly the intersection of
    that window with the h-distant *source slab*, hop windows are disjoint
    (no duplicate ghost images) and their union covers the full window
    whenever ``n_hops >= ceil(r_ghost / min slab width)``. ``n_hops=1`` is
    bitwise the classic single-hop exchange.

    Returns (ghosts, overflow, fill): ``fill`` is this device's largest
    per-side send (not reduced), ``overflow`` its excess over ``ghost_cap``
    reduced over the mesh."""
    ndev = RT.axis_size(axis_name)
    me = RT.axis_index(axis_name)
    xs = ps.x[:, slab_axis]

    send_props = (ps.props if prop_names is None
                  else {k: ps.props[k] for k in prop_names})
    ps_send = ps.replace(props=send_props)

    def send(perm, tree):
        return jax.tree.map(lambda a: RT.ppermute(a, axis_name, perm), tree)

    from_left, from_right, overflows, sends = [], [], [], []
    for h in range(1, n_hops + 1):
        # Selection thresholds, in the *sender's* coordinate frame. The
        # receiver at +h needs our particles with x >= bounds[me+h] - rc
        # (its lower face minus the ghost radius); symmetrically the
        # receiver at -h needs x < bounds[me-h+1] + rc. When the index
        # walks off the bounds array the ring wrapped: fold it back and
        # shift the threshold by ±L. h == 1 can never wrap (bounds[ndev]
        # is the upper box face, bounds[0] the lower), so the classic
        # expressions are kept verbatim — bitwise-identical single-hop.
        if h == 1:
            near_lo = ps.valid & (xs < bounds[me] + r_ghost)
            near_hi = ps.valid & (xs >= bounds[me + 1] - r_ghost)
        else:
            idx_r = me + h
            wrap_r = idx_r > ndev
            idx_r = jnp.where(wrap_r, idx_r - ndev, idx_r)
            thresh_hi = (bounds[idx_r]
                         + jnp.where(wrap_r, box_len, 0.0) - r_ghost)
            idx_l = me - h + 1
            wrap_l = idx_l < 0
            idx_l = jnp.where(wrap_l, idx_l + ndev, idx_l)
            thresh_lo = (bounds[idx_l]
                         - jnp.where(wrap_l, box_len, 0.0) + r_ghost)
            near_lo = ps.valid & (xs < thresh_lo)
            near_hi = ps.valid & (xs >= thresh_hi)

        lo_x, lo_p, lo_v, lo_s, ovf_lo, n_lo = _pack_side(ps_send, near_lo,
                                                          ghost_cap)
        hi_x, hi_p, hi_v, hi_s, ovf_hi, n_hi = _pack_side(ps_send, near_hi,
                                                          ghost_cap)

        right, left = RT.shift_perms(ndev, h)

        # what I receive from my hop-h LEFT neighbor is what it sent rightwards
        fl = send(right, dict(x=hi_x, p=hi_p, v=hi_v, s=hi_s))
        fr = send(left, dict(x=lo_x, p=lo_p, v=lo_v, s=lo_s))

        # Periodic seam: ghosts that crossed the wrap-around link get their
        # slab coordinate shifted by ∓L so they sit just outside our local
        # slab — downstream kernels then never need minimum-image logic.
        if periodic:
            shift_l = jnp.where(me - h < 0, -box_len, 0.0)
            shift_r = jnp.where(me + h >= ndev, box_len, 0.0)
        else:
            # non-periodic: the wrap-around link carries no physical ghosts
            fl["v"] = fl["v"] & (me - h >= 0)
            fr["v"] = fr["v"] & (me + h < ndev)
            shift_l = shift_r = 0.0

        fl["x"] = fl["x"].at[:, slab_axis].add(_sh(shift_l, fl["x"].dtype))
        fr["x"] = fr["x"].at[:, slab_axis].add(_sh(shift_r, fr["x"].dtype))
        from_left.append(fl)
        from_right.append(fr)
        overflows.append(jnp.maximum(ovf_lo, ovf_hi))
        sends.append(jnp.maximum(n_lo, n_hi))

    sides = from_left + from_right   # rows 0..K-1 left hops, K..2K-1 right
    ghosts = GhostLayer(
        x=jnp.stack([s["x"] for s in sides]),
        props=jax.tree.map(lambda *a: jnp.stack(a),
                           *[s["p"] for s in sides]),
        valid=jnp.stack([s["v"] for s in sides]),
        src_slot=jnp.stack([s["s"] for s in sides]),
    )
    ovf, fill = overflows[0], sends[0]
    for o, n in zip(overflows[1:], sends[1:]):
        ovf, fill = jnp.maximum(ovf, o), jnp.maximum(fill, n)
    overflow = RT.pmax(ovf, axis_name)
    return ghosts, overflow, fill.astype(jnp.int32)


def _sh(v, dtype):
    return jnp.asarray(v, dtype)


def _pack_payload(tree, sel: jax.Array, ghost_cap: int):
    """Pack selected rows of a payload pytree into dense (ghost_cap, ...)
    buffers using the same deterministic cumsum-rank slot assignment as
    :func:`_pack_side` — same ``sel`` ⇒ byte-identical slots, no src/valid
    metadata shipped."""
    rank = jnp.cumsum(sel) - 1
    slot = jnp.where(sel & (rank < ghost_cap), rank, ghost_cap)

    def scat(a):
        buf = jnp.zeros((ghost_cap,) + a.shape[1:], a.dtype)
        return buf.at[slot].set(a, mode="drop")

    return jax.tree.map(scat, tree)


@jax.named_scope("ghost_get")
def ghost_update_local(ps: ParticleSet, x_anchor: jax.Array,
                       bounds: jax.Array, r_ghost: float, axis_name: str,
                       ghost_cap: int, *, periodic: bool, box_len: float,
                       slab_axis: int = 0,
                       prop_names: Tuple[str, ...] = (),
                       n_hops: int = 1) -> Dict[str, jax.Array]:
    """Property-subset refresh of an *existing* ghost layer (OpenFPM's
    ``ghost_get<prop...>(SKIP_LABELLING)``): re-ship only the current
    positions (and ``prop_names``) of the same particles a prior
    :func:`ghost_get_local` exchanged — same ppermute pattern, a fraction
    of the bytes, no re-bucketing.

    The stable-slot contract: the send-side selection is re-derived from
    ``x_anchor`` — the positions the ghost layer was *built* from — under
    the same ``bounds``/``r_ghost``/``ghost_cap``. Because :func:`_pack_side`
    assigns slots by a deterministic cumsum rank over the selection mask,
    identical selections produce byte-identical slot permutations, so row
    ``(side, slot)`` here refreshes exactly the ghost that row holds in the
    cached :class:`GhostLayer`. Valid between two structural exchanges
    whenever no ``map()`` ran in between (slots unpermuted) and ``bounds``
    did not move (no rebalance) — exactly the update-step regime of the
    reuse engine (simulation.make_sim_step(reuse=...), DESIGN.md §14).

    Returns ``{"x": (2K, ghost_cap, dim), name: (2K, ghost_cap, ...)}``
    row-aligned with the cached layer; ``valid``/``src_slot`` are *not*
    shipped — the receiver keeps its cached copies (also frozen between
    structural exchanges)."""
    ndev = RT.axis_size(axis_name)
    me = RT.axis_index(axis_name)
    xa = x_anchor[:, slab_axis]

    payload = {"x": ps.x}
    payload.update({k: ps.props[k] for k in prop_names})

    def send(perm, tree):
        return jax.tree.map(lambda a: RT.ppermute(a, axis_name, perm), tree)

    from_left, from_right = [], []
    for h in range(1, n_hops + 1):
        # identical hop thresholds to ghost_get_local, evaluated on the
        # *anchor* coordinates so the selection (and hence the slot
        # permutation) reproduces the build-time exchange bit-for-bit
        if h == 1:
            near_lo = ps.valid & (xa < bounds[me] + r_ghost)
            near_hi = ps.valid & (xa >= bounds[me + 1] - r_ghost)
        else:
            idx_r = me + h
            wrap_r = idx_r > ndev
            idx_r = jnp.where(wrap_r, idx_r - ndev, idx_r)
            thresh_hi = (bounds[idx_r]
                         + jnp.where(wrap_r, box_len, 0.0) - r_ghost)
            idx_l = me - h + 1
            wrap_l = idx_l < 0
            idx_l = jnp.where(wrap_l, idx_l + ndev, idx_l)
            thresh_lo = (bounds[idx_l]
                         - jnp.where(wrap_l, box_len, 0.0) + r_ghost)
            near_lo = ps.valid & (xa < thresh_lo)
            near_hi = ps.valid & (xa >= thresh_hi)

        lo_pk = _pack_payload(payload, near_lo, ghost_cap)
        hi_pk = _pack_payload(payload, near_hi, ghost_cap)

        right, left = RT.shift_perms(ndev, h)
        fl = send(right, hi_pk)
        fr = send(left, lo_pk)

        if periodic:
            shift_l = jnp.where(me - h < 0, -box_len, 0.0)
            shift_r = jnp.where(me + h >= ndev, box_len, 0.0)
        else:
            # non-periodic wrap links carry no physical ghosts; the cached
            # valid mask (built by ghost_get_local) already zeroes them
            shift_l = shift_r = 0.0

        fl["x"] = fl["x"].at[:, slab_axis].add(_sh(shift_l, fl["x"].dtype))
        fr["x"] = fr["x"].at[:, slab_axis].add(_sh(shift_r, fr["x"].dtype))
        from_left.append(fl)
        from_right.append(fr)

    sides = from_left + from_right   # row order matches GhostLayer
    return jax.tree.map(lambda *a: jnp.stack(a), *sides)


# --------------------------------------------------------------------------
# ghost_put(): return ghost contributions to their owners
# --------------------------------------------------------------------------

def ghost_put_local(contrib, ghosts: GhostLayer, ps: ParticleSet,
                    axis_name: str, op: str = "sum"):
    """The ``ghost_put`` mapping (inside shard_map).

    ``contrib`` is a pytree of arrays shaped (2K, ghost_cap, ...) aligned
    with the GhostLayer — the values accumulated on ghost rows during local
    computation. They are sent back to the source device (reversing each
    hop's ring permutation) and merged into the owner's per-particle arrays
    with ``op`` ∈ {sum, max, min}. Returns the merged pytree with leading
    dim = ps.capacity.

    (The paper's third merge mode — 'merge into a list' — is returned to the
    caller as the raw returned buffers: fixed-capacity list semantics.)
    """
    ndev = RT.axis_size(axis_name)
    n_hops = ghosts.n_hops

    def back(perm, tree):
        return jax.tree.map(lambda a: RT.ppermute(a, axis_name, perm), tree)

    returned = []   # (contrib, slot, valid) per ghost row, in row order
    for h in range(1, n_hops + 1):
        right, left = RT.shift_perms(ndev, h)
        # row h-1 came FROM the hop-h left neighbor ⇒ contributions go back
        # left by h; row K+h-1 symmetrically right by h.
        rl, rr = h - 1, n_hops + h - 1
        returned.append((
            back(left, jax.tree.map(lambda a: a[rl], contrib)),
            RT.ppermute(ghosts.src_slot[rl], axis_name, left),
            RT.ppermute(ghosts.valid[rl], axis_name, left)))
        returned.append((
            back(right, jax.tree.map(lambda a: a[rr], contrib)),
            RT.ppermute(ghosts.src_slot[rr], axis_name, right),
            RT.ppermute(ghosts.valid[rr], axis_name, right)))

    cap = ps.capacity

    def merge(base, *chans):
        def one(b, c, slot, v):
            vm = v.reshape(v.shape + (1,) * (c.ndim - 1))
            c = jnp.where(vm, c, _identity(op, c.dtype))
            idx = jnp.where(v, slot, cap)
            if op == "sum":
                return b.at[idx].add(c, mode="drop")
            if op == "max":
                return b.at[idx].max(c, mode="drop")
            if op == "min":
                return b.at[idx].min(c, mode="drop")
            raise ValueError(f"unknown ghost_put op {op!r}")
        b = base
        for c, (_, slot, v) in zip(chans, returned):
            b = one(b, c, slot, v)
        return b

    return jax.tree.map(merge, _zeros_like_for(op, contrib, cap),
                        *[c for c, _, _ in returned])


def _identity(op, dtype):
    if op == "sum":
        return jnp.zeros((), dtype)
    if op == "max":
        return jnp.asarray(jnp.finfo(dtype).min if jnp.issubdtype(dtype, jnp.floating)
                           else jnp.iinfo(dtype).min, dtype)
    if op == "min":
        return jnp.asarray(jnp.finfo(dtype).max if jnp.issubdtype(dtype, jnp.floating)
                           else jnp.iinfo(dtype).max, dtype)
    raise ValueError(op)


def _zeros_like_for(op, contrib, cap):
    def mk(a):
        shape = (cap,) + a.shape[2:]
        return jnp.full(shape, _identity(op, a.dtype), a.dtype)
    return jax.tree.map(mk, contrib)


# --------------------------------------------------------------------------
# shard_map wrappers over globally sharded particle sets
# --------------------------------------------------------------------------

def ps_specs(example: ParticleSet, axis_name: str):
    """PartitionSpecs sharding every ParticleSet leaf on its leading dim."""
    return jax.tree.map(lambda _: P(axis_name), example)


def make_map_fn(mesh: Mesh, example: ParticleSet, axis_name: str,
                bucket_cap: int, slab_axis: int = 0):
    """Jitted global ``map()`` over a ParticleSet sharded along ``axis_name``.

    Returns fn(ps, bounds) -> (ps, overflow)."""
    spec = ps_specs(example, axis_name)

    def fn(ps: ParticleSet, bounds: jax.Array):
        return map_particles_local(ps, bounds, axis_name, bucket_cap,
                                   slab_axis)[:2]

    mapped = RT.shard_map(fn, mesh, in_specs=(spec, P()),
                          out_specs=(spec, P()), check_vma=False)
    return jax.jit(mapped)


def make_ghost_get_fn(mesh: Mesh, example: ParticleSet, axis_name: str,
                      ghost_cap: int, r_ghost: float, *, periodic: bool,
                      box_len: float, slab_axis: int = 0,
                      prop_names: Tuple[str, ...] | None = None,
                      n_hops: int = 1):
    """Jitted global ``ghost_get()``; returns fn(ps, bounds) -> (GhostLayer
    sharded per device, overflow)."""
    spec = ps_specs(example, axis_name)

    def fn(ps: ParticleSet, bounds: jax.Array):
        return ghost_get_local(ps, bounds, r_ghost, axis_name, ghost_cap,
                               periodic=periodic, box_len=box_len,
                               slab_axis=slab_axis, prop_names=prop_names,
                               n_hops=n_hops)[:2]

    # GhostLayer leaves have a local leading dim of 2; globally they stack
    # along a new device axis — shard every leaf on its leading dim.
    send_props = (example.props if prop_names is None
                  else {k: example.props[k] for k in prop_names})
    ghost_example = GhostLayer(x=example.x, props=send_props,
                               valid=example.valid, src_slot=example.valid)
    gspec = jax.tree.map(lambda _: P(axis_name), ghost_example)
    mapped = RT.shard_map(fn, mesh, in_specs=(spec, P()),
                          out_specs=(gspec, P()), check_vma=False)
    return jax.jit(mapped)
