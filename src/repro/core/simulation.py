"""Simulation layer — one particle container, every backend (DESIGN.md §9).

OpenFPM's central claim (paper §3, §4.1) is that a simulation is written
*once* against a distributed particle container (``vector_dist``) and the
framework transparently handles decomposition, migration (``map()``) and
ghost exchange — the user never writes a "distributed version" of their
code. This module is that claim's rendering here:

  * :class:`DistributedParticles` — the transparent container: a
    :class:`~repro.core.particles.ParticleSet` plus the adaptive-slab
    decomposition ``bounds`` it lives under. Serial is the 1-slab special
    case of the same state (``bounds = [box_lo, box_hi]``), not a separate
    code path.
  * :class:`PhysicsSpec` — what an application declares: its domain, cutoff,
    the pair body (the unified cell-pair engine protocol, core/interactions),
    which per-particle fields exist, which of them ghosts carry
    (OpenFPM's property-subset ``ghost_get<prop...>``), and two integrator
    hooks (``advance`` before the pair pass, ``finish`` after it).
  * :func:`make_sim_step` — the engine: composes ``advance`` → ``map()``
    migration → ``ghost_get`` → cell list → unified cell-pair engine
    (jnp | pallas) → ``finish`` into one jitted step. ``mesh=None``
    degenerates to the serial path: the same hooks, the same pair engine,
    the same cell-list plumbing, with the communication stages skipped —
    so serial ≡ 1-device by construction, and every workload written as a
    :class:`PhysicsSpec` shards for free.

Declared fields migrate automatically: ``map()`` communicates the whole
property pytree, so per-step scalars (SPH density/EOS state) and even
per-contact history (DEM tangential springs, keyed by partner particle id)
ride along without app-side plumbing. Ghosts carry only ``ghost_props``.

Every capacity contract is surfaced, never silently dropped
(:class:`StepFlags`): cell-list overflow, neighbor-list overflow, map()
bucket overflow, ghost_get overflow, and the *ghost contract* — the
in-graph check that ``r_ghost <= min slab width`` (the ±1-neighbor
exchange covers the interaction range). Bounds are traced, so the check
stays valid under in-graph dynamic load balancing
(:func:`make_rebalance`, paper §3.5).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from . import cell_list as CL
from . import dlb
from . import grid as G
from . import interactions as I
from . import mappings as M
from . import runtime as RT
from .particles import ParticleSet


# --------------------------------------------------------------------------
# The container
# --------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DistributedParticles:
    """The transparently distributed particle container (``vector_dist``).

    ``ps`` is the particle data (globally sharded along the mesh axis on a
    distributed run; a plain single-device set otherwise). ``bounds`` is the
    adaptive-slab decomposition along the slab axis: device d owns
    ``bounds[d] <= x < bounds[d+1]``. Serial state is the 1-slab case
    ``bounds = [box_lo, box_hi]`` — the same container, every backend.

    ``fields`` holds the mesh state a hybrid particle-mesh physics declares
    (``PhysicsSpec.mesh_props``): each entry is a mesh array whose leading
    axis is the slab axis in mesh rows, sharded alongside the particles on
    a distributed run (full arrays serially — the ``grid.DistributedField``
    pattern riding inside the particle container). Hooks see the local
    blocks plus ``grid.GridOps`` for ghost_get/ghost_put.
    """

    ps: ParticleSet
    bounds: jax.Array       # (n_slabs + 1,) float32
    fields: Dict[str, jax.Array] = dataclasses.field(default_factory=dict)
    # Pencil (2-D mesh) decomposition only (DESIGN.md §13): device (i, j)
    # owns ``bounds[i] <= x0 < bounds[i+1]`` × ``col_bounds[j] <= x1 <
    # col_bounds[j+1]``. None on slab/serial states — the container stays
    # the 1-D type there (an empty pytree subtree, so specs line up).
    col_bounds: Optional[jax.Array] = None

    @property
    def n_slabs(self) -> int:
        return self.bounds.shape[0] - 1


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class StepFlags:
    """Per-step overflow/contract flags (all () int32; 0 = healthy).

    Nonzero means a static capacity must be re-provisioned by the control
    plane (the OpenFPM re-provision contract, DESIGN.md §2) — state stays
    consistent for retained particles, nothing is silently dropped.
    """

    cell: jax.Array            # cell-list bucket excess over cell_cap
    neighbor: jax.Array        # Verlet/contact-list excess over k slots
    bucket: jax.Array          # map() per-destination bucket excess
    ghost: jax.Array           # ghost_get per-side excess over ghost_cap
    ghost_contract: jax.Array  # ghost-hop excess: ceil(r_ghost / min slab
    #                            width) minus the hops the step exchanges
    #                            (DESIGN.md §13). 0 ⇔ the k-hop ghost_get
    #                            covers r_cut; a positive value is how many
    #                            MORE hops the current decomposition needs.
    window: jax.Array = dataclasses.field(  # split-phase interior row-window
        default_factory=lambda: jnp.zeros((), jnp.int32))
    #                            excess (overlap mode): DLB skewed a slab
    #                            past the static interior_rows cap
    stale: jax.Array = dataclasses.field(   # reuse-engine Verlet tripwire
        default_factory=lambda: jnp.zeros((), jnp.int32))
    #                            (DESIGN.md §14): 1 = some particle moved
    #                            > skin/2 since the cached exchange
    #                            structure was built, so this step took the
    #                            full map→ghost_get→rebuild path. Cadence
    #                            telemetry, not an error — excluded from
    #                            ``any()``.
    # Counters, not errors (excluded from ``any()``): the high-water mark
    # each capacity overflow above is computed from — the fullest cell
    # (vs cell_cap), map() bucket (vs bucket_cap) and ghost_get send (vs
    # ghost_cap) — and ``cell_list.candidate_pairs``. A mesh step reports
    # the busiest device's.
    cell_fill: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.zeros((), jnp.int32))
    bucket_fill: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.zeros((), jnp.int32))
    ghost_fill: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.zeros((), jnp.int32))
    candidate_pairs: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.zeros((), jnp.int32))

    def any(self) -> jax.Array:
        """Max over the *error* flags (``stale`` is cadence telemetry, not
        a capacity violation, and is deliberately excluded)."""
        return jnp.maximum(
            jnp.maximum(jnp.maximum(self.cell, self.neighbor),
                        jnp.maximum(self.bucket, self.ghost)),
            jnp.maximum(self.ghost_contract, self.window))


_Z32 = functools.partial(jnp.zeros, (), jnp.int32)


def _pmax_packed(axes, **vals) -> Dict[str, jax.Array]:
    """``vals`` reduced over the mesh in ONE pmax (a packed int32
    vector), returned by name."""
    packed = RT.pmax(jnp.stack([jnp.asarray(v, jnp.int32)
                                for v in vals.values()]), axes)
    return {k: packed[i] for i, k in enumerate(vals)}


def _mesh_flags(axes, done, cl, cell_cap: int,
                **vals) -> Dict[str, jax.Array]:
    """A mesh step's flags and counters, reduced in one packed pmax:
    ``vals`` (``cell_fill`` among them), ``candidate_pairs`` of the step's
    cell list ``cl``, and the ``cell`` flag as the reduced fill's excess
    over ``cell_cap`` (the largest overflow of the step's lists).

    The values and ``cl`` first pass an optimization barrier with ``done``
    (the step's final particles), so the counter and the collective run
    after the step's last pass: they leave the passes' compiled code and
    memory placement as without counters, and the host's flag read
    returns only once every device has finished the step, so no device
    starts the next one early to wait in its first collective."""
    (cl, vals), _ = jax.lax.optimization_barrier(((cl, vals), done))
    out = _pmax_packed(axes, candidate_pairs=CL.candidate_pairs(cl), **vals)
    out["cell"] = jnp.maximum(out["cell_fill"] - cell_cap, 0)
    return out


# --------------------------------------------------------------------------
# Reductions that degenerate: pmax/psum/... on a mesh, identity serially
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Reduce:
    """Global reductions handed to physics hooks. On a distributed step they
    are the mesh collectives; serially they are identities — so hooks write
    e.g. the SPH global dynamic dt once (``red.max(amax)``) and it is
    correct on every backend."""

    axis_name: Optional[str] = None

    @property
    def distributed(self) -> bool:
        return self.axis_name is not None

    def max(self, x):
        return RT.pmax(x, self.axis_name) if self.axis_name else x

    def sum(self, x):
        return RT.psum(x, self.axis_name) if self.axis_name else x

    def mean(self, x):
        return RT.pmean(x, self.axis_name) if self.axis_name else x

    def gather(self, x):
        """(ndev,)-stacked per-shard values (shape (1,) serially)."""
        if self.axis_name:
            return RT.all_gather(x, self.axis_name)
        return jnp.asarray(x)[None]


@dataclasses.dataclass(frozen=True)
class StepCtx:
    """What a ``finish`` hook sees after the pair pass.

    ``ps`` are the local particles (post-``advance``, post-migration);
    ``combo`` is local+ghost (== ``ps`` serially) carrying ``ghost_props``;
    ``cl`` the cell list over ``combo``; ``pair`` the cell-pair engine
    outputs over ``combo`` rows (slice ``[:ps.capacity]`` for the local
    part); ``red`` the backend-degenerate reductions; ``extras`` the
    per-step traced inputs (e.g. SPH's ``euler`` flag).

    ``fields`` are the declared mesh fields (``PhysicsSpec.mesh_props``) as
    local slab blocks (full arrays serially), and ``grid`` the
    backend-degenerate mesh mappings (``grid.GridOps``): ``ghost_get`` to
    pad a block from the slab neighbors, ``ghost_put`` to halo-reduce
    deposited contributions home — so a hybrid physics writes its mesh
    communication once, like it writes its reductions once via ``red``."""

    ps: ParticleSet
    combo: ParticleSet
    cl: CL.CellList
    pair: Dict[str, jax.Array]
    red: Reduce
    extras: Dict[str, Any]
    fields: Dict[str, jax.Array] = dataclasses.field(default_factory=dict)
    grid: G.GridOps = G.GridOps()


# --------------------------------------------------------------------------
# The physics declaration
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PhysicsSpec:
    """A workload, declared once, runnable on every backend.

    A new physics is < 50 lines: a pair body (cell-pair engine protocol),
    the field/ghost declarations, and the two integrator hooks
    (DESIGN.md §9 walks through one).

    Hooks:
      advance(ps, red, extras) -> ps      pre-pair (e.g. MD kick+drift+wrap);
                                          runs before migration so moved
                                          particles are re-owned this step.
      finish(ctx)  -> (ps, scalars, neighbor_overflow[, fields])
                                          post-pair: integrate using
                                          ``ctx.pair`` sums; return per-step
                                          scalars (e.g. SPH dt) and the
                                          overflow of any extra neighbor
                                          structure it built (0 if none).
                                          A 4th element updates the declared
                                          mesh fields (local interior
                                          blocks, same shapes as
                                          ``ctx.fields``).

    ``mesh_props`` declares mesh state carried in
    ``DistributedParticles.fields`` (leading axis = slab axis in mesh
    rows); it lives and communicates alongside the particle fields —
    sharded on a distributed run, whole serially — and reaches ``finish``
    as ``ctx.fields`` + ``ctx.grid`` (ghost_get/ghost_put).

    The reuse-engine declarations (DESIGN.md §14, all optional):
    ``update_props`` are the ghost props an update step refreshes alongside
    positions (OpenFPM's ``ghost_get<prop...>(SKIP_LABELLING)``; default =
    ``pair_props``; DEM needs ``("v", "w")`` because its ``finish`` reads
    ghost angular velocity). ``cache_keys`` names ``finish`` scalars the
    engine lifts out of the scalar dict and carries device-resident across
    steps as physics cache (re-injected into ``extras`` next step, with the
    replicated ``"_reuse_slots_stable"`` flag: True while the combo slot
    permutation is unchanged since the last full rebuild, so slot-indexed
    caches like the DEM contact list stay valid). ``cache_scalars`` marks
    which of those are replicated scalars (the rest shard their leading
    dim); ``cache_example`` builds the zero-valued cache pytree from a
    particle set, seeding the cold cache.
    """

    name: str
    box_lo: Tuple[float, ...]
    box_hi: Tuple[float, ...]
    periodic: Tuple[bool, ...]
    r_cut: float
    cell_cap: int
    pair_out: Dict[str, str]                 # name -> "radial" | "scalar"
    make_body: Callable[[], Any]             # cell-pair engine pair body
    pair_props: Tuple[str, ...] = ()         # props the pair body reads
    ghost_props: Tuple[str, ...] = ()        # props ghosts carry (⊇ pair_props)
    advance: Optional[Callable] = None
    finish: Optional[Callable] = None
    backend: str = "jnp"                     # "jnp" | "pallas"
    interpret: Optional[bool] = None
    precision: str = "fp32"                  # "fp32" | "bf16x" pair engine
    extras_example: Tuple[str, ...] = ()     # names of per-step extras
    bucket_cap: int = 512                    # map() per-destination bucket
    ghost_cap: int = 1024                    # ghost_get per-side capacity
    mesh_props: Tuple[str, ...] = ()         # mesh fields in state.fields
    update_props: Optional[Tuple[str, ...]] = None  # ghost props refreshed
    #                                          on reuse update steps
    #                                          (None → pair_props)
    cache_keys: Tuple[str, ...] = ()         # finish scalars carried as
    #                                          reuse-engine physics cache
    cache_scalars: Tuple[str, ...] = ()      # cache_keys that are replicated
    #                                          scalars (rest shard dim 0)
    cache_example: Optional[Callable] = None  # ps -> zero cache pytree


def _grid_kw(spec: PhysicsSpec, padded_axes: Tuple[int, ...],
             skin: float = 0.0):
    """Cell grid: the declared domain, or (distributed) the ghost-padded box
    — every decomposed space axis in ``padded_axes`` extended by r_cut and
    non-periodic, because ghost images arrive pre-shifted across the seam
    (mappings.ghost_get_local). Serial passes ``()``; a slab run pads its
    one slab axis; a pencil run pads both decomposed axes. A nonzero
    ``skin`` builds the Verlet-margined geometry of the reuse engine
    (DESIGN.md §14): cells and the ghost pad widen to ``r_cut + skin``, so
    a binning built at anchor positions stays pair-complete while no
    particle has moved more than ``skin/2``."""
    lo = list(float(v) for v in spec.box_lo)
    hi = list(float(v) for v in spec.box_hi)
    per = list(bool(v) for v in spec.periodic)
    for ax in padded_axes:
        lo[ax] -= spec.r_cut + skin
        hi[ax] += spec.r_cut + skin
        per[ax] = False
    gs = CL.grid_shape_for(lo, hi, spec.r_cut, skin)
    return dict(box_lo=tuple(lo), box_hi=tuple(hi), grid_shape=gs,
                periodic=tuple(per), cell_cap=spec.cell_cap)


@jax.named_scope("advance")
def _advance(spec: PhysicsSpec, ps: ParticleSet, red: Reduce, extras):
    return ps if spec.advance is None else spec.advance(ps, red, extras)


@jax.named_scope("finish")
def _finish(spec: PhysicsSpec, ctx: StepCtx):
    if spec.finish is None:
        return ctx.ps, {}, _Z32(), ctx.fields
    out = spec.finish(ctx)
    if len(out) == 4:
        ps, scalars, nb_ovf, fields = out
    else:
        ps, scalars, nb_ovf = out
        fields = ctx.fields
    return ps, scalars, jnp.asarray(nb_ovf, jnp.int32), fields


# --------------------------------------------------------------------------
# The engine
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def make_serial_step_fn(physics, cfg, *, slab_axis: int = 0):
    """The serial (1-slab) step composition, UN-jitted.

    ``make_sim_step(physics, cfg)`` is exactly ``jax.jit`` of this
    function; the fleet engine (``repro.fleet.batch``) ``vmap``s it over a
    batch axis instead — serial single-sim is the batch=1 degenerate case
    of the same composition. Cached on ``(physics, cfg, slab_axis)`` like
    the engine itself.
    """
    spec = physics(cfg)
    body = spec.make_body()
    pair_kw = dict(out=spec.pair_out, r_cut=float(spec.r_cut),
                   prop_names=spec.pair_props,
                   backend=spec.backend, interpret=spec.interpret,
                   precision=spec.precision)
    mesh_periodic = bool(spec.periodic[slab_axis])
    cl_kw = _grid_kw(spec, ())

    def step(state: DistributedParticles, extras):
        red = Reduce(None)
        grid = G.GridOps(None, periodic=mesh_periodic)
        ps = state.ps
        ps = _advance(spec, ps, red, extras)
        cl = CL.build_cell_list(ps, **cl_kw)
        pair = I.apply_pair_kernel(ps, cl, body, **pair_kw)
        ps, scalars, nb_ovf, fields = _finish(
            spec, StepCtx(ps=ps, combo=ps, cl=cl, pair=pair, red=red,
                          extras=extras, fields=state.fields, grid=grid))
        flags = StepFlags(cell=jnp.asarray(cl.overflow, jnp.int32),
                          neighbor=nb_ovf, bucket=_Z32(), ghost=_Z32(),
                          ghost_contract=_Z32(), cell_fill=cl.fill,
                          candidate_pairs=CL.candidate_pairs(cl))
        return (dataclasses.replace(state, ps=ps, fields=fields), flags,
                scalars)

    return step


def _auto_hops(rc: float, box_len: float, ndev: int) -> int:
    """Static default ghost-hop count: the hops a *uniform* decomposition of
    ``ndev`` slabs needs to cover ``rc`` (clamped to the ring diameter).
    In-graph the traced bounds re-derive the true need; the excess lands in
    ``StepFlags.ghost_contract``."""
    if ndev <= 1:
        return 1
    need = int(np.ceil(rc * ndev / box_len - 1e-9))
    return max(1, min(ndev - 1, need))


def _slab_geom(cl_kw, slab_axis: int, ndev: int,
               interior_rows: Optional[int]):
    """Static split-phase window geometry over a slab-decomposed cell grid
    (shared by the every-step and reuse engines so their row math cannot
    drift): slab-axis row count, flat-cell strides, the binning-exact
    ``row_of`` coordinate→row map, and whole-row → flat-cell-id expansion.
    """
    gs = cl_kw["grid_shape"]
    n_rows = int(gs[slab_axis])
    n_cells = int(np.prod(gs))
    strides = np.concatenate(
        [np.cumprod(np.asarray(gs)[::-1])[::-1][1:], [1]]).astype(np.int32)
    row_stride = int(strides[slab_axis])
    oshape = list(gs)
    oshape[slab_axis] = 1
    oix = np.indices(oshape).reshape(len(gs), -1)
    # flat cell ids of the slab-row cross-section (row index 0)
    other_offs = jnp.asarray(
        np.sort((oix * strides[:, None]).sum(axis=0)).astype(np.int32))
    lo_s = float(cl_kw["box_lo"][slab_axis])
    hi_s = float(cl_kw["box_hi"][slab_axis])
    w_int = int(interior_rows if interior_rows is not None
                else min(n_rows, -(-n_rows // ndev) + 4))

    def row_of(t):
        """Slab-axis cell row of coordinate t — the exact binning expression
        of cell_list._flat_cell_of, so window edges agree with particle
        homes bit-for-bit (monotone in t)."""
        frac = (t - lo_s) / (hi_s - lo_s)
        return jnp.clip(jnp.floor(frac * n_rows).astype(jnp.int32), 0,
                        n_rows - 1)

    def rows_to_cells(rows, ok):
        """Flat home-cell selection of whole slab rows; masked-out rows
        become inactive sentinels (n_cells)."""
        flat = rows[:, None] * row_stride + other_offs[None, :]
        return jnp.where(ok[:, None], flat, n_cells).reshape(-1)

    return dict(n_rows=n_rows, n_cells=n_cells, w_int=w_int, row_of=row_of,
                rows_to_cells=rows_to_cells)


@functools.lru_cache(maxsize=None)
def make_sim_step(physics, cfg, mesh=None, *, axis_name="shards",
                  slab_axis: int = 0, bucket_cap: Optional[int] = None,
                  ghost_cap: Optional[int] = None, overlap: bool = True,
                  interior_rows: Optional[int] = None,
                  n_hops: Optional[int] = None,
                  reuse: Optional[str] = None,
                  skin: Optional[float] = None):
    """Build the jitted simulation step for ``physics(cfg)``.

    Returns ``step(state, extras) -> (state, flags, scalars)`` over a
    :class:`DistributedParticles` state. ``mesh=None`` builds the serial
    path — the 1-device special case of the same composition; with a mesh
    the identical hooks run inside ``shard_map`` with ``map()``/``ghost_get``
    communication composed around the pair pass.

    ``axis_name`` may be a single mesh axis (slab decomposition) or a
    ``(row_axis, col_axis)`` tuple over a 2-D device mesh (pencil
    decomposition, DESIGN.md §13): particles are decomposed along
    ``slab_axis`` over the rows and ``slab_axis + 1`` over the columns
    (state carries ``col_bounds``), with a two-stage map and a two-stage
    ghost_get (rows first, then columns over locals+row-ghosts, which
    relays corner ghosts). A tuple whose column axis has size 1 runs the
    slab composition over the row axis — bitwise today's 1-D path.

    ``n_hops`` sets the ghost-exchange hop count (per decomposed axis);
    default is the static uniform-width need ``ceil(r_cut·ndev/box_len)``.
    The in-graph re-derivation against the traced (DLB-moved) bounds
    reports any shortfall in ``StepFlags.ghost_contract`` — thin slabs are
    now *satisfied* by extra hops, not merely flagged.

    ``overlap=True`` (the default on a mesh) selects the split-phase
    schedule (DESIGN.md §12): the ghost_get ppermute is issued first, the
    pair engine runs on *interior* cells — restricted to this shard's owned
    cell rows of a locals-only cell list, so it has no data dependence on
    the exchange and XLA's latency-hiding scheduler flies the ppermute
    underneath it — and only the boundary cell rows (within r_cut of the
    slab faces, plus the ghost pad rows) wait for the arrived ghosts. The
    per-particle combine picks the boundary result for particles within
    r_cut of a face and the interior result elsewhere; both are computed
    from identical summand tiles (stable-sort slot packing), so the step
    is bitwise-equal to ``overlap=False`` — the legacy blocking chain
    compute → ghost_get → compute, kept as the benchmark baseline.
    The split-phase window geometry assumes single-hop boundary bands, so
    multi-hop steps (and true 2-D pencil steps) run the blocking schedule.
    ``interior_rows`` caps the static interior row window (default:
    uniform share + margin); a DLB-skewed slab exceeding it raises
    ``StepFlags.window``, never drops interactions silently.

    ``reuse`` selects the two-speed skin-amortized cadence (DESIGN.md §14)
    and changes the step's state type to :class:`ReuseState` (build one
    with :func:`reuse_state`, mirroring these kwargs):

      * ``"skin"`` — the ghost band widens to ``r_cut + skin``, the
        exchange structure (ghost slot permutation + combo cell list) is
        cached, and each step an in-graph pmax'd Verlet tripwire
        (``cell_list.moved_beyond`` against the cached anchors, surfaced as
        ``StepFlags.stale``) drives a ``lax.cond``: fresh cache → the cheap
        update path (no map(), no re-binning; the fixed-payload
        ``mappings.ghost_update_local`` refreshes positions +
        ``update_props`` of the *same* ghost slots); tripped → the full
        map → ghost_get → rebuild path. Correctness is the standard skin/2
        guarantee — no pair within ``r_cut`` is ever missed.
      * ``"update"`` — the pure update path with no rebuild cond (the first
        step after a cold cache still takes the full path to warm it).
        Unsafe beyond skin/2 drift — exists for HLO accounting (the wire
        bytes of an update step in isolation) and cadence experiments.

    ``skin`` is the Verlet margin (default ``0.5 * r_cut``; must be in
    ``(0, r_cut]``). Update steps compose with ``overlap=True``: the
    interior pass runs on the *cached* locals-only binning while the
    (smaller) update ppermute is in flight. On a true 2-D pencil mesh the
    reuse engine degrades gracefully: every step runs the full 2-D path
    (``stale`` = 1 throughout), the state type is still ReuseState.

    ``physics`` must be a module-level callable ``physics(cfg) ->``
    :class:`PhysicsSpec` and ``cfg`` hashable (a frozen config dataclass):
    the engine is cached on ``(physics, cfg, mesh, ...)``.
    """
    if reuse is not None and reuse not in ("skin", "update"):
        raise ValueError(
            f"reuse must be None, 'skin' or 'update'; got {reuse!r}")
    if mesh is None:
        if reuse is not None:
            return jax.jit(_make_reuse_serial_fn(physics, cfg, slab_axis,
                                                 reuse, skin))
        return jax.jit(make_serial_step_fn(physics, cfg,
                                           slab_axis=slab_axis))

    two_d_state = isinstance(axis_name, tuple)
    if two_d_state:
        row_axis, col_axis = axis_name
    else:
        row_axis, col_axis = axis_name, None
    ndev_c = int(mesh.shape[col_axis]) if col_axis is not None else 1

    spec = physics(cfg)
    body = spec.make_body()
    rc = float(spec.r_cut)
    pair_kw = dict(out=spec.pair_out, r_cut=rc, prop_names=spec.pair_props,
                   backend=spec.backend, interpret=spec.interpret,
                   precision=spec.precision)

    b_cap = int(bucket_cap or spec.bucket_cap)
    g_cap = int(ghost_cap or spec.ghost_cap)
    box_len = float(spec.box_hi[slab_axis]) - float(spec.box_lo[slab_axis])
    per_slab = bool(spec.periodic[slab_axis])
    ndev = int(mesh.shape[row_axis])
    k_row = int(n_hops) if n_hops is not None else _auto_hops(rc, box_len,
                                                              ndev)
    if ndev_c > 1:
        inner2d = _make_sim_step_2d(
            spec, body, pair_kw, mesh, row_axis, col_axis, slab_axis,
            b_cap, g_cap, k_row, n_hops)
        if reuse is not None:
            return _wrap_reuse_fallback(inner2d)
        return inner2d

    axis_name = row_axis
    if reuse is not None:
        if two_d_state:
            # pencil-typed state (col_bounds riding, even at ncols=1): run
            # the every-step composition under the inert-cache wrapper
            inner = make_sim_step(
                physics, cfg, mesh, axis_name=(row_axis, col_axis),
                slab_axis=slab_axis, bucket_cap=bucket_cap,
                ghost_cap=ghost_cap, overlap=overlap,
                interior_rows=interior_rows, n_hops=n_hops)
            return _wrap_reuse_fallback(inner)
        return _make_reuse_step_1d(
            spec, body, pair_kw, mesh, axis_name, slab_axis, b_cap, g_cap,
            overlap, interior_rows, n_hops, reuse, skin)
    cl_kw = _grid_kw(spec, (slab_axis,))
    # The split-phase window geometry assumes the single-hop regime
    # (boundary bands one r_cut wide); multi-hop thin slabs fall back to
    # the blocking schedule (ROADMAP follow-on).
    overlap = overlap and k_row == 1

    # --- static split-phase geometry (overlap mode) -----------------------
    geom = _slab_geom(cl_kw, slab_axis, ndev, interior_rows)
    n_rows, w_int = geom["n_rows"], geom["w_int"]
    _row_of, _rows_to_cells = geom["row_of"], geom["rows_to_cells"]
    W_B = 5   # boundary rows per side: <= 3 needed (cell width >= r_cut,
    #           so [face - r_cut, face + r_cut] spans <= 3 rows) + 1 margin
    #           each way for fp32 seam-shift rounding

    def local_step(state: DistributedParticles, extras):
        red = Reduce(axis_name)
        grid = G.GridOps(axis_name, periodic=per_slab)
        ps, bounds = state.ps, state.bounds
        ps = _advance(spec, ps, red, extras)
        # map(): migrate to owners under the (possibly DLB-moved) bounds
        ps, ovf_bucket, fill_bucket = M.map_particles_local(
            ps, bounds, axis_name, b_cap, slab_axis)
        # ghost contract (DESIGN.md §13): the k-hop exchange covers r_cut
        # while k >= ceil(r_ghost / min slab width). Bounds are traced (DLB
        # moves them in-graph), so the need is re-derived in-graph; the
        # flag reports the hop *excess* still missing (0 = satisfied).
        contract = _hop_excess(bounds, rc, k_row)
        ghosts, ovf_ghost, fill_ghost = M.ghost_get_local(
            ps, bounds, rc, axis_name, g_cap, periodic=per_slab,
            box_len=box_len, slab_axis=slab_axis, prop_names=spec.ghost_props,
            n_hops=k_row)
        win_ovf = _Z32()
        if overlap:
            # Interior pass while the ghost ppermute is in flight: a
            # locals-only cell list (no ghost dependence) restricted to
            # this shard's owned rows. Boundary particles in these cells
            # get ghost-less garbage here — overwritten by the combine.
            me = RT.axis_index(axis_name)
            my_lo, my_hi = bounds[me], bounds[me + 1]
            r0 = _row_of(my_lo)
            r_last = _row_of(my_hi)
            int_rows = r0 + jnp.arange(w_int, dtype=jnp.int32)
            with jax.named_scope("pair_interior"):
                cl_loc = CL.build_cell_list(ps, **cl_kw)
                pair_int = I.apply_pair_kernel(
                    ps, cl_loc, body,
                    cells=_rows_to_cells(int_rows, int_rows < n_rows),
                    **pair_kw)
            win_ovf = jnp.maximum(r_last + 1 - (r0 + w_int), 0)
        gp = ghosts.as_particles()
        combo = ParticleSet(
            x=jnp.concatenate([ps.x, gp.x]),
            props={k: jnp.concatenate([ps.props[k], gp.props[k]])
                   for k in spec.ghost_props},
            valid=jnp.concatenate([ps.valid, gp.valid]))
        if overlap:
            # Boundary pass against the arrived ghosts: the rows within
            # r_cut of either slab face plus the ghost pad rows, hi side
            # deduplicated against lo so no cell scatters twice.
            with jax.named_scope("pair_boundary"):
                cl = CL.build_cell_list(combo, **cl_kw)
                lo_rows = (_row_of(my_lo - rc) - 1
                           + jnp.arange(W_B, dtype=jnp.int32))
                hi_rows = (_row_of(my_hi - rc) - 1
                           + jnp.arange(W_B, dtype=jnp.int32))
                lo_ok = (lo_rows >= 0) & (lo_rows < n_rows)
                hi_ok = ((hi_rows >= 0) & (hi_rows < n_rows)
                         & (hi_rows > lo_rows[-1]))
                bnd_cells = jnp.concatenate(
                    [_rows_to_cells(lo_rows, lo_ok),
                     _rows_to_cells(hi_rows, hi_ok)])
                pair_bnd = I.apply_pair_kernel(combo, cl, body,
                                               cells=bnd_cells, **pair_kw)
            # combine per particle: boundary result within r_cut of a face
            # (and for all ghost rows), interior result elsewhere
            xs = ps.x[:, slab_axis]
            bnd = (xs < my_lo + rc) | (xs >= my_hi - rc)
            n_loc = ps.capacity
            pair = {k: jnp.concatenate(
                [jnp.where(I._bmask(bnd, pair_bnd[k][:n_loc]),
                           pair_bnd[k][:n_loc], pair_int[k]),
                 pair_bnd[k][n_loc:]])
                for k in pair_bnd}
            cl_fill = jnp.maximum(cl.fill, cl_loc.fill)
        else:
            cl = CL.build_cell_list(combo, **cl_kw)
            pair = I.apply_pair_kernel(combo, cl, body, **pair_kw)
            cl_fill = cl.fill
        ps, scalars, nb_ovf, fields = _finish(
            spec, StepCtx(ps=ps, combo=combo, cl=cl, pair=pair, red=red,
                          extras=extras, fields=state.fields, grid=grid))
        flags = StepFlags(
            bucket=jnp.asarray(ovf_bucket, jnp.int32),
            ghost=jnp.asarray(ovf_ghost, jnp.int32),
            ghost_contract=contract,
            **_mesh_flags(axis_name, ps, cl, cl_kw["cell_cap"],
                          neighbor=nb_ovf, window=win_ovf, cell_fill=cl_fill,
                          bucket_fill=fill_bucket, ghost_fill=fill_ghost))
        return (dataclasses.replace(state, ps=ps, fields=fields), flags,
                scalars)

    state_spec = _state_spec(spec, axis_name,
                             with_col_bounds=two_d_state)
    stepped = RT.shard_map(local_step, mesh,
                           in_specs=(state_spec, P()),
                           out_specs=(state_spec, P(), P()),
                           check_vma=False)
    return jax.jit(stepped)


def _hop_excess(bounds: jax.Array, rc: float, k: int) -> jax.Array:
    """In-graph ghost-contract check against traced slab bounds: how many
    hops ``ceil(rc / min width)`` needs beyond the ``k`` exchanged (>= 0;
    0 = the k-hop ghost_get covers r_cut)."""
    min_w = jnp.maximum(jnp.min(bounds[1:] - bounds[:-1]), 1e-12)
    k_needed = jnp.ceil(rc / min_w).astype(jnp.int32)
    return jnp.maximum(k_needed - k, 0).astype(jnp.int32)


def _state_spec(spec: PhysicsSpec, axis_name, *,
                with_col_bounds: bool = False) -> DistributedParticles:
    """shard_map specs for the container: particles and declared mesh
    fields shard their leading dim, bounds replicate. ``axis_name`` may be
    a tuple of mesh axes (pencil decomposition: the leading dim shards over
    their product, row-major); ``with_col_bounds`` adds the replicated
    column-bounds leaf pencil states carry."""
    part = P(axis_name)
    return DistributedParticles(
        ps=part, bounds=P(),
        fields={k: part for k in spec.mesh_props},
        col_bounds=P() if with_col_bounds else None)


def _make_sim_step_2d(spec: PhysicsSpec, body, pair_kw, mesh, row_axis: str,
                      col_axis: str, slab_axis: int, b_cap: int, g_cap: int,
                      k_row: int, n_hops: Optional[int]):
    """The pencil (2-D device mesh) step composition (DESIGN.md §13):
    two-stage map, two-stage multi-hop ghost_get (columns exchange
    locals+row-ghosts, relaying corner ghosts), one blocking pair pass over
    a cell box ghost-padded on both decomposed axes."""
    if spec.mesh_props:
        raise NotImplementedError(
            "mesh_props on a true 2-D device mesh needs the pencil GridOps "
            "(ROADMAP follow-on); decompose mesh-carrying physics as "
            "(ndev, 1) or use apps/vortex.py's pencil VIC step")
    col_space_axis = slab_axis + 1
    if col_space_axis >= len(spec.box_lo):
        raise ValueError("pencil decomposition needs a space axis "
                         f"{col_space_axis}; physics is {len(spec.box_lo)}-D")
    rc = float(spec.r_cut)
    box_len_c = (float(spec.box_hi[col_space_axis])
                 - float(spec.box_lo[col_space_axis]))
    box_len_r = float(spec.box_hi[slab_axis]) - float(spec.box_lo[slab_axis])
    per_row = bool(spec.periodic[slab_axis])
    per_col = bool(spec.periodic[col_space_axis])
    ndev_c = int(mesh.shape[col_axis])
    k_col = (int(n_hops) if n_hops is not None
             else _auto_hops(rc, box_len_c, ndev_c))
    axes = (row_axis, col_axis)
    cl_kw = _grid_kw(spec, (slab_axis, col_space_axis))

    def local_step(state: DistributedParticles, extras):
        red = Reduce(axes)
        ps, bounds, cbounds = state.ps, state.bounds, state.col_bounds
        ps = _advance(spec, ps, red, extras)
        # two-stage map(): rows re-own along slab_axis within each mesh
        # column, then columns re-own along col_space_axis within each row
        ps, ovf_r, fill_r = M.map_particles_local(ps, bounds, row_axis,
                                                  b_cap, slab_axis)
        ps, ovf_c, fill_c = M.map_particles_local(ps, cbounds, col_axis,
                                                  b_cap, col_space_axis)
        ovf_bucket = jnp.maximum(ovf_r, ovf_c)
        contract = jnp.maximum(_hop_excess(bounds, rc, k_row),
                               _hop_excess(cbounds, rc, k_col))
        # two-stage ghost_get: rows first; the column exchange then ships
        # locals+row-ghosts, so corner particles relay via the (row, col∓1)
        # neighbor — no dedicated diagonal sends.
        ghosts_r, ovf_gr, fill_gr = M.ghost_get_local(
            ps, bounds, rc, row_axis, g_cap, periodic=per_row,
            box_len=box_len_r, slab_axis=slab_axis,
            prop_names=spec.ghost_props, n_hops=k_row)
        gp_r = ghosts_r.as_particles()
        combo_r = ParticleSet(
            x=jnp.concatenate([ps.x, gp_r.x]),
            props={k: jnp.concatenate([ps.props[k], gp_r.props[k]])
                   for k in spec.ghost_props},
            valid=jnp.concatenate([ps.valid, gp_r.valid]))
        ghosts_c, ovf_gc, fill_gc = M.ghost_get_local(
            combo_r, cbounds, rc, col_axis, g_cap, periodic=per_col,
            box_len=box_len_c, slab_axis=col_space_axis,
            prop_names=spec.ghost_props, n_hops=k_col)
        gp_c = ghosts_c.as_particles()
        combo = ParticleSet(
            x=jnp.concatenate([combo_r.x, gp_c.x]),
            props={k: jnp.concatenate([combo_r.props[k], gp_c.props[k]])
                   for k in spec.ghost_props},
            valid=jnp.concatenate([combo_r.valid, gp_c.valid]))
        cl = CL.build_cell_list(combo, **cl_kw)
        pair = I.apply_pair_kernel(combo, cl, body, **pair_kw)
        ps, scalars, nb_ovf, fields = _finish(
            spec, StepCtx(ps=ps, combo=combo, cl=cl, pair=pair, red=red,
                          extras=extras, fields=state.fields,
                          grid=G.GridOps()))
        flags = StepFlags(
            ghost_contract=contract, window=_Z32(),
            **_mesh_flags(axes, ps, cl, cl_kw["cell_cap"], neighbor=nb_ovf,
                          bucket=ovf_bucket,
                          ghost=jnp.maximum(ovf_gr, ovf_gc),
                          cell_fill=cl.fill,
                          bucket_fill=jnp.maximum(fill_r, fill_c),
                          ghost_fill=jnp.maximum(fill_gr, fill_gc)))
        return (dataclasses.replace(state, ps=ps, fields=fields), flags,
                scalars)

    state_spec = _state_spec(spec, axes, with_col_bounds=True)
    stepped = RT.shard_map(local_step, mesh,
                           in_specs=(state_spec, P()),
                           out_specs=(state_spec, P(), P()),
                           check_vma=False)
    return jax.jit(stepped)


# --------------------------------------------------------------------------
# The reuse engine: skin-amortized two-speed cadence (DESIGN.md §14)
# --------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ReuseCache:
    """The cached exchange *structure* the reuse engine carries across steps
    (OpenFPM's ghost layer as a cache, paper §4.1): the anchor positions the
    structure was built from, the combo cell-list binning, the ghost layer
    (slot permutation + static props; its positions are the build-time
    anchors), the locals-only binning of the split-phase schedule, and any
    physics cache the spec declared (``cache_keys``, e.g. the DEM contact
    list). ``ok=False`` marks a cold cache — the next step takes the full
    rebuild path unconditionally."""

    ok: jax.Array                      # () bool: cache warm?
    x_anchor: jax.Array                # (cap, dim) positions at build
    cl: CL.CellList                    # combo binning at build
    ghosts: Optional[M.GhostLayer] = None   # cached layer (None serially)
    cl_loc: Optional[CL.CellList] = None    # locals-only binning (overlap)
    phys: Dict[str, jax.Array] = dataclasses.field(default_factory=dict)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ReuseState:
    """A :class:`DistributedParticles` riding with its reuse cache — the
    state type of ``make_sim_step(..., reuse=...)`` steps. Build with
    :func:`reuse_state`; read results from ``.inner``."""

    inner: DistributedParticles
    cache: ReuseCache


def _resolve_skin(spec: PhysicsSpec, skin: Optional[float]) -> float:
    rc = float(spec.r_cut)
    skin_v = float(skin) if skin is not None else 0.5 * rc
    if not 0.0 < skin_v <= rc:
        raise ValueError(
            f"reuse skin must be in (0, r_cut]; got {skin_v} (r_cut={rc})")
    return skin_v


def _combo_of(ps: ParticleSet, ghosts: M.GhostLayer,
              prop_names) -> ParticleSet:
    gp = ghosts.as_particles()
    return ParticleSet(
        x=jnp.concatenate([ps.x, gp.x]),
        props={k: jnp.concatenate([ps.props[k], gp.props[k]])
               for k in prop_names},
        valid=jnp.concatenate([ps.valid, gp.valid]))


@functools.lru_cache(maxsize=None)
def _make_reuse_serial_fn(physics, cfg, slab_axis, reuse, skin):
    """Serial reuse step: the cadence degenerates to cached-binning reuse
    (no exchange to amortize), driven by the same tripwire — the 1-slab
    special case of the same two-speed composition, so serial ≡ 1-device
    holds for the reuse engine too."""
    spec = physics(cfg)
    body = spec.make_body()
    skin_v = _resolve_skin(spec, skin)
    pair_kw = dict(out=spec.pair_out, r_cut=float(spec.r_cut),
                   prop_names=spec.pair_props, backend=spec.backend,
                   interpret=spec.interpret, precision=spec.precision)
    mesh_periodic = bool(spec.periodic[slab_axis])
    cl_kw = _grid_kw(spec, (), skin=skin_v)

    def step(rstate: ReuseState, extras):
        state, cache = rstate.inner, rstate.cache
        red = Reduce(None)
        grid = G.GridOps(None, periodic=mesh_periodic)
        ps = state.ps
        ps = _advance(spec, ps, red, extras)
        moved = CL.moved_beyond(ps.x, cache.x_anchor, ps.valid, skin_v)
        stale = ((~cache.ok) | moved).astype(jnp.int32)
        take_full = (stale > 0) if reuse == "skin" else ~cache.ok
        cl = jax.lax.cond(take_full,
                          lambda _: CL.build_cell_list(ps, **cl_kw),
                          lambda _: cache.cl, None)
        pair = I.apply_pair_kernel(ps, cl, body, **pair_kw)
        extras_f = extras
        if spec.cache_keys:
            # serial slots never permute (no map), so slot-indexed physics
            # caches stay valid across rebuilds too
            extras_f = {**extras, **cache.phys,
                        "_reuse_slots_stable": jnp.ones((), bool)}
        ps2, scalars, nb_ovf, fields = _finish(
            spec, StepCtx(ps=ps, combo=ps, cl=cl, pair=pair, red=red,
                          extras=extras_f, fields=state.fields, grid=grid))
        phys_new = cache.phys
        if spec.cache_keys:
            scalars = dict(scalars)
            phys_new = {k: scalars.pop(k) for k in spec.cache_keys}
        new_cache = ReuseCache(
            ok=jnp.ones((), bool),
            x_anchor=jnp.where(take_full, ps.x, cache.x_anchor),
            cl=cl, ghosts=None, cl_loc=None, phys=phys_new)
        flags = StepFlags(cell=jnp.asarray(cl.overflow, jnp.int32),
                          neighbor=nb_ovf, bucket=_Z32(), ghost=_Z32(),
                          ghost_contract=_Z32(), stale=stale,
                          cell_fill=cl.fill,
                          candidate_pairs=CL.candidate_pairs(cl))
        inner = dataclasses.replace(state, ps=ps2, fields=fields)
        return ReuseState(inner=inner, cache=new_cache), flags, scalars

    return step


def _make_reuse_step_1d(spec: PhysicsSpec, body, pair_kw, mesh, axis_name,
                        slab_axis: int, b_cap: int, g_cap: int,
                        overlap: bool, interior_rows: Optional[int],
                        n_hops: Optional[int], reuse: str,
                        skin: Optional[float]):
    """The two-speed 1-D slab step (DESIGN.md §14).

    Every step issues the fixed-payload ``mappings.ghost_update_local``
    (positions + ``update_props`` of the cached ghost slots, re-derived
    from the cached anchors so the slot permutation is byte-identical) and
    evaluates the pmax'd Verlet tripwire on locals-vs-anchors. A
    ``lax.cond`` then runs either the cheap update path — cached combo
    binning, merged refreshed ghosts, and (overlap mode) the interior pair
    pass on the cached locals-only binning while the update ppermute is in
    flight — or the full map → ghost_get(r_cut+skin) → rebuild path.
    Correctness is the standard skin/2 guarantee: cells and ghost band are
    ``r_cut + skin`` wide, so the cached structure is pair-complete for
    ``r_cut`` until some particle drifts past skin/2 — exactly when the
    tripwire forces the rebuild."""
    rc = float(spec.r_cut)
    skin_v = _resolve_skin(spec, skin)
    r_g = rc + skin_v
    box_len = float(spec.box_hi[slab_axis]) - float(spec.box_lo[slab_axis])
    per_slab = bool(spec.periodic[slab_axis])
    ndev = int(mesh.shape[axis_name])
    k_row = (int(n_hops) if n_hops is not None
             else _auto_hops(r_g, box_len, ndev))
    overlap = bool(overlap) and k_row == 1
    cl_kw = _grid_kw(spec, (slab_axis,), skin=skin_v)
    upd_props = (spec.update_props if spec.update_props is not None
                 else spec.pair_props)
    geom = _slab_geom(cl_kw, slab_axis, ndev, interior_rows)
    n_rows, w_int = geom["n_rows"], geom["w_int"]
    row_of, rows_to_cells = geom["row_of"], geom["rows_to_cells"]
    W_B = 5   # boundary rows per side: the combine band is r_cut+skin wide
    #           and cached anchors lag current positions by <= skin/2, so
    #           the band's build rows span <= 2 + (skin/2)/(r_cut+skin)
    #           <= 2.25 cell widths -> <= 4 rows, +1 low margin

    def local_step(rstate: ReuseState, extras):
        state, cache = rstate.inner, rstate.cache
        red = Reduce(axis_name)
        grid = G.GridOps(axis_name, periodic=per_slab)
        ps, bounds = state.ps, state.bounds
        ps = _advance(spec, ps, red, extras)

        # Fixed-payload refresh of the cached ghost slots — always issued,
        # before the cadence decision: the update path consumes it (its
        # interior pair pass hides the in-flight ppermute), the full path
        # discards it. Slot selection re-derives from the cached anchors,
        # so the slots are byte-identical to the cached layer's.
        upd = M.ghost_update_local(
            ps, cache.x_anchor, bounds, r_g, axis_name, g_cap,
            periodic=per_slab, box_len=box_len, slab_axis=slab_axis,
            prop_names=upd_props, n_hops=k_row)

        # Verlet tripwire (StepFlags.stale): locals against their build
        # anchors, pmax'd. Every ghost is some device's local with the same
        # anchor (seam shifts are constant between rebuilds), so the global
        # max covers the ghost band too — and by not reading the in-flight
        # update payload, the cadence decision doesn't serialize on it.
        moved = CL.moved_beyond(ps.x, cache.x_anchor, ps.valid, skin_v)
        stale = RT.pmax(((~cache.ok) | moved).astype(jnp.int32), axis_name)
        if reuse == "update":
            take_full = RT.pmax((~cache.ok).astype(jnp.int32),
                                axis_name) > 0
        else:
            take_full = stale > 0

        contract = _hop_excess(bounds, r_g, k_row)
        me = RT.axis_index(axis_name)
        my_lo, my_hi = bounds[me], bounds[me + 1]
        win_ovf = _Z32()
        if overlap:
            r0 = row_of(my_lo)
            r_last = row_of(my_hi)
            int_rows = r0 + jnp.arange(w_int, dtype=jnp.int32)
            int_cells = rows_to_cells(int_rows, int_rows < n_rows)
            win_ovf = jnp.maximum(r_last + 1 - (r0 + w_int), 0)
            lo_rows = (row_of(my_lo - r_g) - 1
                       + jnp.arange(W_B, dtype=jnp.int32))
            hi_rows = (row_of(my_hi - r_g) - 1
                       + jnp.arange(W_B, dtype=jnp.int32))
            lo_ok = (lo_rows >= 0) & (lo_rows < n_rows)
            hi_ok = ((hi_rows >= 0) & (hi_rows < n_rows)
                     & (hi_rows > lo_rows[-1]))
            bnd_cells = jnp.concatenate([rows_to_cells(lo_rows, lo_ok),
                                         rows_to_cells(hi_rows, hi_ok)])

        def full_branch(ps):
            ps2, ovf_b, fill_b = M.map_particles_local(ps, bounds, axis_name,
                                                       b_cap, slab_axis)
            ghosts, ovf_g, fill_g = M.ghost_get_local(
                ps2, bounds, r_g, axis_name, g_cap, periodic=per_slab,
                box_len=box_len, slab_axis=slab_axis,
                prop_names=spec.ghost_props, n_hops=k_row)
            combo = _combo_of(ps2, ghosts, spec.ghost_props)
            cl = CL.build_cell_list(combo, **cl_kw)
            pair = I.apply_pair_kernel(combo, cl, body, **pair_kw)
            cl_loc = CL.build_cell_list(ps2, **cl_kw) if overlap else None
            return (ps2, ghosts, combo, cl, cl_loc, pair,
                    jnp.asarray(ovf_b, jnp.int32),
                    jnp.asarray(ovf_g, jnp.int32), fill_b, fill_g)

        def update_branch(ps):
            # SKIP_LABELLING: same slots, refreshed positions + update
            # props; everything else (valid mask, src slots, static props,
            # both binnings) comes from the cache.
            gprops = dict(cache.ghosts.props)
            for k in upd_props:
                gprops[k] = upd[k]
            ghosts = M.GhostLayer(x=upd["x"], props=gprops,
                                  valid=cache.ghosts.valid,
                                  src_slot=cache.ghosts.src_slot)
            combo = _combo_of(ps, ghosts, spec.ghost_props)
            cl = cache.cl
            if overlap:
                with jax.named_scope("pair_interior"):
                    pair_int = I.apply_pair_kernel(
                        ps, cache.cl_loc, body, cells=int_cells, **pair_kw)
                with jax.named_scope("pair_boundary"):
                    pair_bnd = I.apply_pair_kernel(
                        combo, cl, body, cells=bnd_cells, **pair_kw)
                # the combine band widens by the skin: cached ghosts can
                # have drifted up to skin/2 INTO the slab since build, so
                # a particle needs the ghost-aware result within
                # r_cut + skin of a face
                xs = ps.x[:, slab_axis]
                bnd = (xs < my_lo + r_g) | (xs >= my_hi - r_g)
                n_loc = ps.capacity
                pair = {k: jnp.concatenate(
                    [jnp.where(I._bmask(bnd, pair_bnd[k][:n_loc]),
                               pair_bnd[k][:n_loc], pair_int[k]),
                     pair_bnd[k][n_loc:]])
                    for k in pair_bnd}
            else:
                pair = I.apply_pair_kernel(combo, cl, body, **pair_kw)
            return (ps, ghosts, combo, cl, cache.cl_loc, pair, _Z32(),
                    _Z32(), _Z32(), _Z32())

        (ps2, ghosts, combo, cl, cl_loc, pair, ovf_bucket, ovf_ghost,
         fill_bucket, fill_ghost) = jax.lax.cond(take_full, full_branch,
                                                 update_branch, ps)

        extras_f = extras
        if spec.cache_keys:
            extras_f = {**extras, **cache.phys,
                        "_reuse_slots_stable": jnp.logical_not(take_full)}
        ps3, scalars, nb_ovf, fields = _finish(
            spec, StepCtx(ps=ps2, combo=combo, cl=cl, pair=pair, red=red,
                          extras=extras_f, fields=state.fields, grid=grid))
        phys_new = cache.phys
        if spec.cache_keys:
            scalars = dict(scalars)
            phys_new = {k: scalars.pop(k) for k in spec.cache_keys}

        # cached scalars must be replicated (out_specs P()): pmax the
        # per-device overflow counters and fills before storing
        cl_store = dataclasses.replace(cl, **_pmax_packed(
            axis_name, overflow=cl.overflow, fill=cl.fill))
        cell_fill = cl_store.fill
        cl_loc_store = None
        if overlap:
            cl_loc_store = dataclasses.replace(cl_loc, **_pmax_packed(
                axis_name, overflow=cl_loc.overflow, fill=cl_loc.fill))
            cell_fill = jnp.maximum(cell_fill, cl_loc_store.fill)

        def sel(new, old):
            return jnp.where(take_full, new, old)

        new_cache = ReuseCache(
            ok=jnp.ones((), bool),
            x_anchor=sel(ps2.x, cache.x_anchor),
            cl=cl_store,
            # on an update step keep the cached layer (anchor positions),
            # not the refreshed one — the slot metadata is identical
            ghosts=jax.tree.map(sel, ghosts, cache.ghosts),
            cl_loc=cl_loc_store,
            phys=phys_new)
        flags = StepFlags(
            bucket=jnp.asarray(ovf_bucket, jnp.int32),
            ghost=jnp.asarray(ovf_ghost, jnp.int32),
            ghost_contract=contract, stale=stale,
            **_mesh_flags(axis_name, ps3, cl, cl_kw["cell_cap"],
                          neighbor=nb_ovf, window=win_ovf,
                          cell_fill=cell_fill, bucket_fill=fill_bucket,
                          ghost_fill=fill_ghost))
        inner = dataclasses.replace(state, ps=ps3, fields=fields)
        return ReuseState(inner=inner, cache=new_cache), flags, scalars

    rspec = _reuse_state_spec(spec, axis_name, cl_kw, overlap)
    stepped = RT.shard_map(local_step, mesh, in_specs=(rspec, P()),
                           out_specs=(rspec, P(), P()), check_vma=False)
    return jax.jit(stepped)


def _wrap_reuse_fallback(inner_step):
    """Graceful reuse degradation (true 2-D pencil meshes / pencil-typed
    states): the cache rides inert and every step runs the full inner
    composition — same ``ReuseState`` signature, ``StepFlags.stale`` = 1
    throughout, no amortization (pencil reuse is a ROADMAP follow-on)."""
    def step(rstate: ReuseState, extras):
        inner, flags, scalars = inner_step(rstate.inner, extras)
        flags = dataclasses.replace(flags, stale=jnp.ones((), jnp.int32))
        return ReuseState(inner=inner, cache=rstate.cache), flags, scalars
    return step


def _reuse_state_spec(spec: PhysicsSpec, axis_name, cl_kw,
                      overlap: bool) -> ReuseState:
    """shard_map specs for :class:`ReuseState`: cache arrays shard their
    leading dim alongside the particles; the warm flag, cell-list overflow
    counters and declared ``cache_scalars`` replicate."""
    part, rep = P(axis_name), P()
    cl_spec = CL.CellList(
        cells=part, counts=part, cell_id=part, overflow=rep, fill=rep,
        grid_shape=tuple(cl_kw["grid_shape"]),
        periodic=tuple(cl_kw["periodic"]),
        box_lo=tuple(cl_kw["box_lo"]), box_hi=tuple(cl_kw["box_hi"]))
    cache_spec = ReuseCache(
        ok=rep, x_anchor=part, cl=cl_spec,
        ghosts=M.GhostLayer(x=part,
                            props={k: part for k in spec.ghost_props},
                            valid=part, src_slot=part),
        cl_loc=cl_spec if overlap else None,
        phys={k: (rep if k in spec.cache_scalars else part)
              for k in spec.cache_keys})
    return ReuseState(inner=_state_spec(spec, axis_name), cache=cache_spec)


def _cold_cell_list(cl_kw, rows_lead: int, id_lead: int,
                    sentinel: int) -> CL.CellList:
    """An all-empty cell list with the right static geometry and (global)
    leading dims — the cold-cache placeholder ``reuse_state`` installs; its
    contents are never read (``ok=False`` forces the full path first)."""
    n_cells = int(np.prod(cl_kw["grid_shape"]))
    return CL.CellList(
        cells=jnp.full((rows_lead, int(cl_kw["cell_cap"])), sentinel,
                       jnp.int32),
        counts=jnp.zeros((rows_lead,), jnp.int32),
        cell_id=jnp.full((id_lead,), n_cells, jnp.int32),
        overflow=jnp.zeros((), jnp.int32),
        fill=jnp.zeros((), jnp.int32),
        grid_shape=tuple(cl_kw["grid_shape"]),
        periodic=tuple(cl_kw["periodic"]),
        box_lo=tuple(cl_kw["box_lo"]), box_hi=tuple(cl_kw["box_hi"]))


def reuse_state(state: DistributedParticles, physics, cfg, mesh=None, *,
                axis_name="shards", slab_axis: int = 0,
                ghost_cap: Optional[int] = None, overlap: bool = True,
                n_hops: Optional[int] = None,
                skin: Optional[float] = None) -> ReuseState:
    """Wrap a container for the reuse engine with a COLD cache: the first
    step takes the full map → ghost_get → rebuild path unconditionally and
    warms it. Mirror the kwargs you pass ``make_sim_step`` — they shape the
    cached structure (grid geometry, hop count, overlap binning). Call it
    again after any out-of-step re-decomposition (``make_rebalance``): a
    moved slab boundary invalidates the cached slot permutation."""
    spec = physics(cfg)
    skin_v = _resolve_skin(spec, skin)
    phys = {}
    if spec.cache_keys:
        if spec.cache_example is None:
            raise ValueError(
                "PhysicsSpec.cache_keys needs cache_example to seed the "
                "cold reuse cache")
        ex = spec.cache_example(state.ps)
        phys = {k: ex[k] for k in spec.cache_keys}
    if mesh is None or isinstance(axis_name, tuple):
        # serial, or the pencil/pencil-typed fallback (cache rides inert)
        cl_kw = _grid_kw(spec, (), skin=skin_v)
        cap = state.ps.capacity
        cache = ReuseCache(
            ok=jnp.zeros((), bool), x_anchor=state.ps.x,
            cl=_cold_cell_list(cl_kw,
                               int(np.prod(cl_kw["grid_shape"])) + 1,
                               cap, cap),
            ghosts=None, cl_loc=None, phys=phys)
        return ReuseState(inner=state, cache=cache)

    rc = float(spec.r_cut)
    g_cap = int(ghost_cap or spec.ghost_cap)
    box_len = float(spec.box_hi[slab_axis]) - float(spec.box_lo[slab_axis])
    ndev = int(mesh.shape[axis_name])
    k_row = (int(n_hops) if n_hops is not None
             else _auto_hops(rc + skin_v, box_len, ndev))
    overlap = bool(overlap) and k_row == 1
    cl_kw = _grid_kw(spec, (slab_axis,), skin=skin_v)
    ps = state.ps
    cap = ps.capacity
    if cap % ndev:
        raise ValueError(f"capacity {cap} not divisible by {ndev} shards")
    cap_loc = cap // ndev
    n_cells = int(np.prod(cl_kw["grid_shape"]))
    K2 = 2 * k_row
    combo_loc = cap_loc + K2 * g_cap
    ghosts = M.GhostLayer(
        x=jnp.zeros((ndev * K2, g_cap, ps.x.shape[1]), ps.x.dtype),
        props={k: jnp.zeros((ndev * K2, g_cap) + ps.props[k].shape[1:],
                            ps.props[k].dtype) for k in spec.ghost_props},
        valid=jnp.zeros((ndev * K2, g_cap), bool),
        src_slot=jnp.full((ndev * K2, g_cap), cap_loc, jnp.int32))
    cache = ReuseCache(
        ok=jnp.zeros((), bool), x_anchor=ps.x,
        cl=_cold_cell_list(cl_kw, ndev * (n_cells + 1), ndev * combo_loc,
                           combo_loc),
        ghosts=ghosts,
        cl_loc=(_cold_cell_list(cl_kw, ndev * (n_cells + 1), cap, cap_loc)
                if overlap else None),
        phys=phys)
    rstate = ReuseState(inner=state, cache=cache)
    # lay the cache out per the step's specs (prefix-expanded per subtree)
    rspec = _reuse_state_spec(spec, axis_name, cl_kw, overlap)
    is_p = lambda v: isinstance(v, P)
    spec_def = jax.tree.structure(rspec, is_leaf=is_p)
    specs = jax.tree.leaves(rspec, is_leaf=is_p)
    parts = spec_def.flatten_up_to(rstate)
    placed = [jax.device_put(sub, NamedSharding(mesh, p))
              for p, sub in zip(specs, parts)]
    return jax.tree.unflatten(spec_def, placed)


@functools.lru_cache(maxsize=None)
def make_rebalance(physics, cfg, mesh, *, axis_name="shards",
                   slab_axis: int = 0, bucket_cap: Optional[int] = None,
                   nbins: int = 256, min_slab_width: Optional[float] = None,
                   n_hops: int = 1):
    """The DLB 'repartition + migrate' pair (paper §3.5), physics-generic:
    cost-balanced slab bounds from the global particle histogram (psum'd
    in-graph) followed by ``map()`` under the new decomposition. The new
    bounds are projected onto slabs >= ``min_slab_width`` (default:
    r_cut / ``n_hops`` — a step exchanging ``n_hops`` ghost hops covers
    r_cut across slabs that thin, DESIGN.md §13) so the balancer can never
    move the decomposition into ghost-contract violation.

    ``axis_name`` may be a ``(row_axis, col_axis)`` tuple (pencil states):
    each decomposed axis is rebalanced against its own psum'd histogram and
    particles re-owned along rows then columns; ``col_bounds`` rides in the
    state. Returns ``fn(state) -> (state, overflow)``."""
    spec = physics(cfg)
    two_d_state = isinstance(axis_name, tuple)
    if two_d_state:
        row_axis, col_axis = axis_name
        ndev_c = int(mesh.shape[col_axis])
    else:
        row_axis, col_axis, ndev_c = axis_name, None, 1
    col_space_axis = slab_axis + 1
    ndev = int(mesh.shape[row_axis])
    lo = float(spec.box_lo[slab_axis])
    hi = float(spec.box_hi[slab_axis])
    b_cap = int(bucket_cap or spec.bucket_cap)
    # 0.1% margin keeps cumsum rounding from landing a hair under the
    # per-hop reach r_cut / n_hops
    min_w = float(spec.r_cut * 1.001 / max(int(n_hops), 1)
                  if min_slab_width is None else min_slab_width)
    red_axes = axis_name  # tuple → psum over the whole device mesh

    def local(state: DistributedParticles):
        ps = state.ps
        hist = dlb.histogram_cost(ps.x[:, slab_axis],
                                  jnp.where(ps.valid, 1.0, 0.0),
                                  lo, hi, nbins)
        hist = RT.psum(hist, red_axes)
        new_bounds = dlb.bounds_from_histogram(hist, ndev, lo, hi)
        new_bounds = dlb.enforce_min_width(new_bounds, min_w)
        ps, ovf, _ = M.map_particles_local(ps, new_bounds, row_axis, b_cap,
                                           slab_axis)
        new_cbounds = state.col_bounds
        if ndev_c > 1:
            lo_c = float(spec.box_lo[col_space_axis])
            hi_c = float(spec.box_hi[col_space_axis])
            hist_c = dlb.histogram_cost(ps.x[:, col_space_axis],
                                        jnp.where(ps.valid, 1.0, 0.0),
                                        lo_c, hi_c, nbins)
            hist_c = RT.psum(hist_c, red_axes)
            new_cbounds = dlb.bounds_from_histogram(hist_c, ndev_c, lo_c,
                                                    hi_c)
            new_cbounds = dlb.enforce_min_width(new_cbounds, min_w)
            ps, ovf_c, _ = M.map_particles_local(ps, new_cbounds, col_axis,
                                                 b_cap, col_space_axis)
            ovf = jnp.maximum(ovf, ovf_c)
        if two_d_state:
            ovf = RT.pmax(ovf, red_axes)
        # mesh fields stay put: DLB moves the PARTICLE slab bounds only —
        # the mesh decomposition is the uniform row split of the arrays
        return (DistributedParticles(ps=ps, bounds=new_bounds,
                                     fields=state.fields,
                                     col_bounds=new_cbounds), ovf)

    sm_axis = axis_name if ndev_c > 1 else row_axis
    state_spec = _state_spec(spec, sm_axis, with_col_bounds=two_d_state)
    fn = RT.shard_map(local, mesh, in_specs=(state_spec,),
                      out_specs=(state_spec, P()), check_vma=False)
    return jax.jit(fn)


# --------------------------------------------------------------------------
# State construction: serial and scattered
# --------------------------------------------------------------------------

def with_ids(ps: ParticleSet) -> ParticleSet:
    """Ensure an int32 ``id`` prop (dense index among valid rows) — the
    provenance key serial-vs-distributed comparisons and DEM contact
    history match on."""
    if "id" in ps.props:
        return ps
    val = np.asarray(ps.valid)
    ids = np.cumsum(val) - 1
    return ps.with_prop("id", jnp.asarray(np.where(val, ids, 0), np.int32))


@functools.lru_cache(maxsize=None)
def _serial_bounds(lo: float, hi: float) -> jax.Array:
    return jnp.asarray([lo, hi], jnp.float32)


def serial_state(ps: ParticleSet, physics, cfg, slab_axis: int = 0,
                 fields: Optional[Dict[str, jax.Array]] = None
                 ) -> DistributedParticles:
    """The 1-slab (serial) container: same state type, trivial bounds."""
    spec = physics(cfg)
    return DistributedParticles(
        ps=ps, bounds=_serial_bounds(float(spec.box_lo[slab_axis]),
                                     float(spec.box_hi[slab_axis])),
        fields=dict(fields or {}))


def distribute(ps0: ParticleSet, physics, cfg, mesh, *,
               axis_name="shards", slab_axis: int = 0,
               cap_per_dev: Optional[int] = None, cap_factor: float = 3.0,
               bounds: Optional[jax.Array] = None,
               col_bounds: Optional[jax.Array] = None,
               fields: Optional[Dict[str, jax.Array]] = None
               ) -> DistributedParticles:
    """Host-side 'global map' (paper: distributed read + global map):
    scatter every valid particle of ``ps0`` into its owning device's slot
    block (device d owns slots [d·cap, (d+1)·cap)), add the ``id`` prop,
    and shard the result over ``mesh``. ``fields`` (full mesh arrays,
    leading axis = slab axis rows) are sharded alongside.

    ``axis_name`` may be a ``(row_axis, col_axis)`` tuple (pencil
    decomposition, DESIGN.md §13): device (i, j) owns the slab-axis slab i
    × the ``slab_axis + 1`` column slab j, its slot block is flat index
    ``i·ncols + j`` (the mesh's row-major device order, matching
    ``P((row_axis, col_axis))`` sharding of the leading dim), and the state
    carries ``col_bounds``."""
    spec = physics(cfg)
    two_d = isinstance(axis_name, tuple)
    if two_d:
        row_axis, col_axis = axis_name
        ndev_r = int(mesh.shape[row_axis])
        ndev_c = int(mesh.shape[col_axis])
        if fields:
            raise NotImplementedError(
                "mesh fields on a true 2-D device mesh need the pencil "
                "GridOps (ROADMAP follow-on); decompose field-carrying "
                "physics as (ndev, 1) slabs or use apps/vortex.py's "
                "pencil VIC step")
    else:
        ndev_r, ndev_c = int(mesh.shape[axis_name]), 1
    ndev = ndev_r * ndev_c
    col_space_axis = slab_axis + 1
    ps0 = with_ids(ps0)
    val0 = np.asarray(ps0.valid)
    xs = np.asarray(ps0.x)[val0]
    props = {k: np.asarray(v)[val0] for k, v in ps0.props.items()}
    n = len(xs)
    if cap_per_dev is None:
        cap_per_dev = int(np.ceil(n / ndev * cap_factor))
    if bounds is None:
        bounds = dlb.uniform_bounds(ndev_r, float(spec.box_lo[slab_axis]),
                                    float(spec.box_hi[slab_axis]))
    owner = np.clip(
        np.searchsorted(np.asarray(bounds), xs[:, slab_axis], "right") - 1,
        0, ndev_r - 1)
    if two_d:
        if col_bounds is None:
            col_bounds = dlb.uniform_bounds(
                ndev_c, float(spec.box_lo[col_space_axis]),
                float(spec.box_hi[col_space_axis]))
        owner_c = np.clip(
            np.searchsorted(np.asarray(col_bounds), xs[:, col_space_axis],
                            "right") - 1, 0, ndev_c - 1)
        owner = owner * ndev_c + owner_c
    cap = ndev * cap_per_dev
    X = np.full((cap, xs.shape[1]), ParticleSet.FILL, np.float32)
    PR = {k: np.zeros((cap,) + v.shape[1:], v.dtype)
          for k, v in props.items()}
    V = np.zeros(cap, bool)
    for d in range(ndev):
        rows = np.nonzero(owner == d)[0]
        assert len(rows) <= cap_per_dev, "raise cap_per_dev"
        b = d * cap_per_dev
        X[b:b + len(rows)] = xs[rows]
        for k in PR:
            PR[k][b:b + len(rows)] = props[k][rows]
        V[b:b + len(rows)] = True
    ps = ParticleSet(x=jnp.asarray(X),
                     props={k: jnp.asarray(v) for k, v in PR.items()},
                     valid=jnp.asarray(V))
    sh = NamedSharding(mesh, P(axis_name))
    ps = jax.device_put(ps, jax.tree.map(lambda _: sh, ps))
    rep = NamedSharding(mesh, P())
    bounds = jax.device_put(jnp.asarray(bounds, jnp.float32), rep)
    if two_d:
        col_bounds = jax.device_put(jnp.asarray(col_bounds, jnp.float32),
                                    rep)
    for k, v in (fields or {}).items():
        if v.shape[0] % ndev:
            raise ValueError(
                f"mesh field {k!r}: leading axis {v.shape[0]} not divisible "
                f"by {ndev} shards (GridOps.first_row assumes uniform slabs)")
    sharded_fields = {k: jax.device_put(v, sh)
                      for k, v in (fields or {}).items()}
    return DistributedParticles(ps=ps, bounds=bounds, fields=sharded_fields,
                                col_bounds=col_bounds if two_d else None)
