"""Hybrid particle-mesh Vortex-in-Cell method (paper §4.4, Algorithm 1).

Incompressible Navier-Stokes in vorticity form on a 3D periodic box:
  Dω/Dt = (ω·∇)u + ν∆ω ,   ∆ψ = -ω ,  u = ∇×ψ.

Per step (two-stage RK with remeshing, M'4 interpolations):
  1. solve the vector Poisson equation for ψ (FFT — the PetSc replacement)
  2. u = ∇×ψ; RHS = (ω·∇)u + ν∆ω on the mesh
  3. interpolate u, RHS to particles (M2P, M'4)
  4. move particles / update particle vorticity (RK2)
  5. interpolate vorticity back to the mesh (P2M, M'4) and remesh

Steps 3–5 route through the particle–mesh interpolation subsystem: the
remeshing engine (``core.remesh``) re-seeds particles on mesh nodes above
``remesh_threshold`` each step, and ``use_pallas=True`` switches the M'4
legs from the jnp oracle (``core.interp``) to the fused Pallas kernels
(``kernels.m4_interp`` — one M2P pass interpolates u AND the RHS).

Validation (paper): the vortex ring self-propels along its axis — the
vorticity centroid advances — while total circulation stays bounded.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import interp as IP
from repro.core import remesh as RM
from repro.numerics import poisson as PS


@dataclasses.dataclass(frozen=True)
class VortexConfig:
    shape: Tuple[int, int, int] = (64, 32, 32)   # paper: 1600x400x400
    lengths: Tuple[float, float, float] = (22.0, 5.57, 5.57)
    nu: float = 1.0 / 3750.0                     # Re = 3750 (paper)
    dt: float = 0.0125
    ring_R: float = 1.0
    ring_sigma: float = 1.0 / 3.531
    gamma: float = 1.0
    # particle–mesh interpolation subsystem (steps 3–5)
    use_pallas: bool = False          # kernels/m4_interp instead of core/interp
    precision: str = "fp32"           # "fp32" | "bf16x" M'4 Pallas-leg mode
    remesh_threshold: float = 0.0     # |ω| node re-seed cutoff (0 = all nodes)
    interp_cb: int = 4                # mesh nodes per interpolation cell/axis
    interp_cell_cap: int = 0          # particle slots per cell (0 = auto)
    # distributed mesh phase: ghost rows per side for the M2P gather blocks
    # and the P2M deposit blocks (M'4 support needs 2; the rest absorbs
    # per-step advection across the slab face — overflow is surfaced when
    # a particle outruns it)
    mesh_halo: int = 3


def _axes(cfg):
    return [np.arange(n) * (L / n) for n, L in zip(cfg.shape, cfg.lengths)]


def init_ring(cfg: VortexConfig) -> jax.Array:
    """Paper eq. (8): ω0 = Γ/(πσ²) exp(-s/σ) ring around the z(-here x0)
    axis, center at the box center of the transverse plane."""
    ax = _axes(cfg)
    Z, X, Y = np.meshgrid(*ax, indexing="ij")  # axis 0 is the long axis
    zc = cfg.lengths[0] * 0.25
    xc = cfg.lengths[1] / 2
    yc = cfg.lengths[2] / 2
    rho = np.sqrt((X - xc) ** 2 + (Y - yc) ** 2)
    s2 = (Z - zc) ** 2 + (rho - cfg.ring_R) ** 2
    mag = cfg.gamma / (np.pi * cfg.ring_sigma ** 2) * np.exp(
        -s2 / cfg.ring_sigma ** 2)
    # azimuthal direction in the transverse (X, Y) plane
    denom = np.maximum(rho, 1e-9)
    tx = -(Y - yc) / denom
    ty = (X - xc) / denom
    w = np.stack([np.zeros_like(mag), mag * tx, mag * ty], axis=-1)
    return jnp.asarray(w, jnp.float32)


def _d(field, axis, h):
    return (jnp.roll(field, -1, axis=axis) - jnp.roll(field, 1, axis=axis)) \
        / (2.0 * h)


def curl(f, hs):
    """f: (..., 3) -> ∇×f with periodic central differences."""
    fx, fy, fz = f[..., 0], f[..., 1], f[..., 2]
    cx = _d(fz, 1, hs[1]) - _d(fy, 2, hs[2])
    cy = _d(fx, 2, hs[2]) - _d(fz, 0, hs[0])
    cz = _d(fy, 0, hs[0]) - _d(fx, 1, hs[1])
    return jnp.stack([cx, cy, cz], axis=-1)


def divergence(f, hs):
    return sum(_d(f[..., d], d, hs[d]) for d in range(3))


def laplacian_vec(f, hs):
    out = []
    for c in range(3):
        g = f[..., c]
        acc = jnp.zeros_like(g)
        for d in range(3):
            acc = acc + (jnp.roll(g, -1, axis=d) - 2 * g
                         + jnp.roll(g, 1, axis=d)) / hs[d] ** 2
        out.append(acc)
    return jnp.stack(out, axis=-1)


def project_divfree(w, cfg: VortexConfig):
    """Helmholtz projection (Algorithm 1 line 3): ω ← ω - ∇(∆⁻¹ ∇·ω)."""
    hs = [L / n for n, L in zip(cfg.shape, cfg.lengths)]
    div = divergence(w, hs)
    phi = PS.fft_poisson(div, cfg.lengths)
    grad = jnp.stack([_d(phi, d, hs[d]) for d in range(3)], axis=-1)
    return w - grad


def velocity_from_vorticity(w, cfg: VortexConfig):
    with jax.named_scope("poisson"):
        psi = PS.fft_poisson(-w, cfg.lengths)
    hs = [L / n for n, L in zip(cfg.shape, cfg.lengths)]
    with jax.named_scope("stencil"):
        return curl(psi, hs)


@jax.named_scope("stencil")
def rhs_field(w, u, cfg: VortexConfig):
    """(ω·∇)u + ν∆ω on the mesh (second-order central, paper §4.4)."""
    hs = [L / n for n, L in zip(cfg.shape, cfg.lengths)]
    stretch = sum(w[..., d:d + 1] * _d(u, d, hs[d]) for d in range(3))
    return stretch + cfg.nu * laplacian_vec(w, hs)


def _mesh_particles(cfg):
    ax = _axes(cfg)
    g = np.stack(np.meshgrid(*ax, indexing="ij"), -1).reshape(-1, 3)
    return jnp.asarray(g, jnp.float32)


def _interp_ops(cfg: VortexConfig, kw):
    """Steps 3/5 backends per config flag: ``bucket`` builds (or skips) the
    per-position-set cell bucketing, which the fused m2p / p2m reuse — the
    RK2 stage interpolates twice at x1 but buckets it once."""
    if cfg.use_pallas:
        from repro.kernels.m4_interp import ops as M4
        pk = dict(cb=cfg.interp_cb, **kw)

        def bucket(x, valid):
            return M4.bucket_particles(x, valid,
                                       cell_cap=cfg.interp_cell_cap, **pk)

        def m2p2(b, fa, fb, x, valid):
            return M4.m2p_fused_bucketed(b, (fa, fb), valid,
                                         precision=cfg.precision, **pk)

        def p2m_(b, x, val, valid):
            return M4.p2m_bucketed(b, val, precision=cfg.precision, **pk)

        def ovf(b):
            return b.overflow
    else:
        def bucket(x, valid):
            return None

        def m2p2(b, fa, fb, x, valid):
            return IP.m2p(fa, x, valid, **kw), IP.m2p(fb, x, valid, **kw)

        def p2m_(b, x, val, valid):
            return IP.p2m(x, val, valid, **kw)

        def ovf(b):
            return jnp.zeros((), jnp.int32)
    return bucket, m2p2, p2m_, ovf


@partial(jax.jit, static_argnames=("cfg",))
def vic_step(w, cfg: VortexConfig):
    """One RK2 step with remeshing. w: (nx,ny,nz,3) mesh vorticity.
    Returns (w_next, overflow) — overflow counts particles dropped by
    interpolation-cell capacity (Pallas path only; 0 on the jnp path).
    Non-zero means re-provision ``interp_cell_cap`` (see :func:`run`)."""
    kw = dict(shape=cfg.shape, box_lo=(0.0, 0.0, 0.0),
              box_hi=cfg.lengths, periodic=(True, True, True))
    bucket, m2p2, p2m_, ovf = _interp_ops(cfg, kw)
    # remeshing engine: re-seed particles on significant mesh nodes
    with jax.named_scope("remesh"):
        ps, _ = RM.seed_from_mesh(w, box_lo=kw["box_lo"],
                                  box_hi=kw["box_hi"],
                                  periodic=kw["periodic"],
                                  threshold=cfg.remesh_threshold, dim=3)
    x0, wp0, valid = ps.x, ps.props["w"], ps.valid

    # stage 1
    b0 = bucket(x0, valid)
    u0 = velocity_from_vorticity(w, cfg)
    r0 = rhs_field(w, u0, cfg)
    up, rp = m2p2(b0, u0, r0, x0, valid)
    x1 = x0 + cfg.dt * up
    wp1 = wp0 + cfg.dt * rp
    # P2M of stage-1 state
    L = jnp.asarray(cfg.lengths, x1.dtype)
    x1 = jnp.where(valid[:, None], jnp.mod(x1, L), x1)
    b1 = bucket(x1, valid)
    w1 = p2m_(b1, x1, wp1, valid)
    # stage 2 at the predicted state
    u1 = velocity_from_vorticity(w1, cfg)
    r1 = rhs_field(w1, u1, cfg)
    up1, rp1 = m2p2(b1, u1, r1, x1, valid)
    # combine (midpoint average), move from x0
    xf = jnp.where(valid[:, None],
                   jnp.mod(x0 + 0.5 * cfg.dt * (up + up1), L), x0)
    wpf = wp0 + 0.5 * cfg.dt * (rp + rp1)
    bf = bucket(xf, valid)
    wf = p2m_(bf, xf, wpf, valid)
    overflow = ovf(b0) + ovf(b1) + ovf(bf)
    return wf, overflow


def centroid_z(w, cfg: VortexConfig) -> jax.Array:
    """|ω|-weighted centroid along the propagation (first) axis."""
    mag = jnp.linalg.norm(w, axis=-1)
    z = jnp.arange(cfg.shape[0], dtype=jnp.float32) * (
        cfg.lengths[0] / cfg.shape[0])
    wz = jnp.sum(mag, axis=(1, 2))
    return jnp.sum(z * wz) / jnp.maximum(jnp.sum(wz), 1e-9)


def enstrophy(w) -> jax.Array:
    return 0.5 * jnp.mean(jnp.sum(w * w, axis=-1))


def step_reprovision(w, cfg: VortexConfig):
    """vic_step plus its control plane: on bucket overflow, double
    ``interp_cell_cap`` and redo the step (the OpenFPM re-provision
    contract). Returns (w_next, cfg) — cfg may have grown. The jnp path
    skips the host sync entirely (overflow is structurally zero there), so
    steps still dispatch asynchronously. Host spans on the profiler's
    clock: ``vic.dispatch``, ``vic.overflow_read``, ``vic.reprovision``."""
    with jax.profiler.TraceAnnotation("vic.dispatch"):
        w2, ovf = vic_step(w, cfg)
    if cfg.use_pallas:
        from repro.kernels.m4_interp.ops import default_cell_cap
        while True:
            with jax.profiler.TraceAnnotation("vic.overflow_read"):
                if int(ovf) <= 0:
                    break
            with jax.profiler.TraceAnnotation("vic.reprovision"):
                cap = (cfg.interp_cell_cap
                       or default_cell_cap(cfg.interp_cb, 3))
                cfg = dataclasses.replace(cfg, interp_cell_cap=2 * cap)
                w2, ovf = vic_step(w, cfg)
    return w2, cfg


def run(cfg: VortexConfig, n_steps: int):
    w = project_divfree(init_ring(cfg), cfg)
    z0 = float(centroid_z(w, cfg))
    for _ in range(n_steps):
        w, cfg = step_reprovision(w, cfg)
    return w, z0, float(centroid_z(w, cfg))


# --------------------------------------------------------------------------
# Distributed phase: sharded mesh fields AND sharded particles
# --------------------------------------------------------------------------

def make_distributed_vic_step(mesh, cfg: VortexConfig,
                              axis_name="shards", *,
                              stencil_overlap: bool = True):
    """Fully sharded VIC step: the mesh half lives in a
    ``grid.DistributedField`` (slab along the long axis) exactly as the
    particle half lives in ``DistributedParticles`` — no replicated
    vorticity/velocity arrays and no full-mesh ``psum`` anywhere.

    ``axis_name`` may be a ``(row_axis, col_axis)`` tuple over an (r, c)
    2-D device mesh (pencil decomposition, DESIGN.md §13): the field
    pencil-shards axes 0 AND 1, the Poisson solve runs the two-transpose
    pencil FFT (``poisson.fft_poisson_pencil_local``), stencils/halos use
    the 2-D ghost protocol (``grid.apply_stencil_local2`` /
    ``halo_pad2`` / ``halo_reduce2``) and the M'4 legs their pencil-block
    forms. A tuple whose column axis has size 1 runs the slab composition
    over the row axis — bitwise today's 1-D path.

    Per stage, on each shard's local slab block:
      * re-seed particles from the LOCAL block only (``RM.seed_from_block``
        — the per-slab remesh; ownership is the slab geometry carried in
        the field's type);
      * Poisson solve via the slab-decomposed FFT
        (``poisson.fft_poisson_slab_local`` — one all_to_all transpose);
      * curl / RHS as halo-1 ghost_get stencils
        (``grid.apply_stencil_local``, the make_stencil_step engine);
      * M'4 M2P against ``mesh_halo``-padded ghost_get blocks
        (``IP.m2p_block``);
      * M'4 P2M into a ``local + mesh_halo`` block followed by the
        ``ghost_put`` halo-reduce (``grid.halo_reduce``) — the O(halo)
        neighbor exchange that replaces the old O(full-mesh) psum.

    Returns ``step(f: grid.DistributedField) -> (f, overflow)`` where
    overflow (replicated int32) counts re-seed surplus plus particles
    whose M'4 support outran ``mesh_halo`` (re-provision ``mesh_halo``).
    jnp interpolation path; the Pallas bucketed kernels stay a
    single-device VMEM optimization (their block legs are
    ``kernels.m4_interp.ops.p2m_block``/``m2p_fused_block``)."""
    if cfg.use_pallas:
        raise NotImplementedError(
            "distributed VIC uses the jnp interpolation oracle; "
            "use_pallas is a single-device VMEM optimization")
    from jax.sharding import PartitionSpec as P
    from repro.core import grid as G
    from repro.core import runtime as RT

    if isinstance(axis_name, tuple):
        row_axis, col_axis = axis_name
        if int(mesh.shape[col_axis]) > 1:
            return _make_pencil_vic_step(mesh, cfg, row_axis, col_axis)
        axis_name = row_axis   # (r, 1) degenerates to the slab composition
    ndev = int(mesh.shape[axis_name])
    n0, n1, _ = cfg.shape
    if n0 % ndev or n1 % ndev:
        raise ValueError(
            f"shape {cfg.shape}: axes 0 and 1 must divide over {ndev} "
            "shards (slab rows + FFT transpose)")
    n0l = n0 // ndev
    H = int(cfg.mesh_halo)
    if not 2 <= H <= n0l:
        raise ValueError(
            f"mesh_halo={H} must be in [2, {n0l}] (M'4 support; single-hop "
            "ghost exchange)")
    kw = dict(shape=cfg.shape, box_lo=(0.0, 0.0, 0.0),
              box_hi=cfg.lengths, periodic=(True, True, True))
    hs = [L / n for n, L in zip(cfg.shape, cfg.lengths)]
    # stencil_overlap: the two-slot halo mode — the halo-1 ppermutes are
    # issued first and interior mesh rows are differenced while the faces
    # are in flight (split-phase stepping, DESIGN.md §12); False keeps the
    # blocking ghost_get chain as the A/B baseline
    curl_st = G.apply_stencil_local(lambda p: curl(p, hs), 1, axis_name,
                                    overlap=stencil_overlap)
    rhs_st = G.apply_stencil_local(
        lambda wp, up: rhs_field(wp, up, cfg), 1, axis_name,
        overlap=stencil_overlap)

    def local_step(f: G.DistributedField):
        me = RT.axis_index(axis_name)
        w = f.data                                    # (n0l, n1, n2, 3)
        row_lo = f.node_bounds[me]
        row0 = row_lo - H                             # padded-block origin
        ps, seed_ovf = RM.seed_from_block(
            w, row_lo, threshold=cfg.remesh_threshold, **kw)
        x0, wp0, valid = ps.x, ps.props["w"], ps.valid
        ovf = seed_ovf

        def eval_fields(wf):
            """ψ solve + curl + RHS, all on local blocks."""
            psi = PS.fft_poisson_slab_local(-wf, cfg.lengths, axis_name)
            (u,) = curl_st(psi)
            (r,) = rhs_st(wf, u)
            return u, r

        def gather(fld, x):
            """M2P against a ghost_get-padded block."""
            pad = G.halo_pad(fld, H, axis_name, periodic=True)
            return IP.m2p_block(pad, x, valid, row0, **kw)

        def deposit(x, wp):
            """P2M into the local+halo block, then ghost_put halo-reduce."""
            blk, drop = IP.p2m_block(x, wp, valid, row0,
                                     block_rows=n0l + 2 * H, **kw)
            return G.halo_reduce(blk, H, axis_name, periodic=True), drop

        # stage 1
        u0, r0 = eval_fields(w)
        up, d0 = gather(u0, x0)
        rp, d1 = gather(r0, x0)
        L = jnp.asarray(cfg.lengths, x0.dtype)
        x1 = jnp.where(valid[:, None], jnp.mod(x0 + cfg.dt * up, L), x0)
        wp1 = wp0 + cfg.dt * rp
        w1, d2 = deposit(x1, wp1)
        # stage 2 at the predicted state
        u1, r1 = eval_fields(w1)
        up1, d3 = gather(u1, x1)
        rp1, d4 = gather(r1, x1)
        xf = jnp.where(valid[:, None],
                       jnp.mod(x0 + 0.5 * cfg.dt * (up + up1), L), x0)
        wpf = wp0 + 0.5 * cfg.dt * (rp + rp1)
        wf, d5 = deposit(xf, wpf)
        ovf = ovf + d0 + d1 + d2 + d3 + d4 + d5
        return (dataclasses.replace(f, data=wf),
                RT.psum(ovf, axis_name))

    stepped = RT.shard_map(local_step, mesh,
                           in_specs=(G.field_spec(axis_name),),
                           out_specs=(G.field_spec(axis_name), P()),
                           check_vma=False)
    return jax.jit(stepped)


def _make_pencil_vic_step(mesh, cfg: VortexConfig, row_axis: str,
                          col_axis: str):
    """The pencil (2-D device mesh) VIC composition (DESIGN.md §13): same
    RK2 per stage as the slab step, with the field pencil-sharded over axes
    0 and 1 — ψ via the two-transpose pencil FFT, stencils over 2-D halos,
    M'4 against 2-D ghost-padded blocks, deposits halo-reduced on both
    decomposed axes (corners relay through the edge neighbors)."""
    from jax.sharding import PartitionSpec as P
    from repro.core import grid as G
    from repro.core import runtime as RT

    ndev_r = int(mesh.shape[row_axis])
    ndev_c = int(mesh.shape[col_axis])
    n0, n1, n2 = cfg.shape
    if n0 % ndev_r or n1 % ndev_c:
        raise ValueError(
            f"shape {cfg.shape}: axis 0 must divide over {ndev_r} row "
            f"shards and axis 1 over {ndev_c} column shards (pencil blocks)")
    if n1 % ndev_r or n2 % ndev_c:
        raise ValueError(
            f"shape {cfg.shape}: the pencil FFT transposes need axis 1 "
            f"divisible by {ndev_r} and axis 2 by {ndev_c}")
    n0l, n1l = n0 // ndev_r, n1 // ndev_c
    H = int(cfg.mesh_halo)
    if not 2 <= H <= min(n0l, n1l):
        raise ValueError(
            f"mesh_halo={H} must be in [2, {min(n0l, n1l)}] (M'4 support; "
            "single-hop ghost exchange per mesh axis)")
    kw = dict(shape=cfg.shape, box_lo=(0.0, 0.0, 0.0),
              box_hi=cfg.lengths, periodic=(True, True, True))
    hs = [L / n for n, L in zip(cfg.shape, cfg.lengths)]
    curl_st = G.apply_stencil_local2(lambda p: curl(p, hs), 1, row_axis,
                                     col_axis)
    rhs_st = G.apply_stencil_local2(
        lambda wp, up: rhs_field(wp, up, cfg), 1, row_axis, col_axis)

    def local_step(f: G.DistributedField):
        me_r = RT.axis_index(row_axis)
        me_c = RT.axis_index(col_axis)
        w = f.data                                    # (n0l, n1l, n2, 3)
        row_lo = f.node_bounds[me_r]
        col_lo = f.col_bounds[me_c]
        row0, col0 = row_lo - H, col_lo - H           # padded-block origin
        ps, seed_ovf = RM.seed_from_block2(
            w, row_lo, col_lo, threshold=cfg.remesh_threshold, **kw)
        x0, wp0, valid = ps.x, ps.props["w"], ps.valid
        ovf = seed_ovf

        def eval_fields(wf):
            psi = PS.fft_poisson_pencil_local(-wf, cfg.lengths, row_axis,
                                              col_axis)
            (u,) = curl_st(psi)
            (r,) = rhs_st(wf, u)
            return u, r

        def gather(fld, x):
            pad = G.halo_pad2(fld, H, row_axis, col_axis, periodic=True)
            return IP.m2p_block2(pad, x, valid, row0, col0, **kw)

        def deposit(x, wp):
            blk, drop = IP.p2m_block2(x, wp, valid, row0, col0,
                                      block_rows=n0l + 2 * H,
                                      block_cols=n1l + 2 * H, **kw)
            return (G.halo_reduce2(blk, H, row_axis, col_axis,
                                   periodic=True), drop)

        # stage 1
        u0, r0 = eval_fields(w)
        up, d0 = gather(u0, x0)
        rp, d1 = gather(r0, x0)
        L = jnp.asarray(cfg.lengths, x0.dtype)
        x1 = jnp.where(valid[:, None], jnp.mod(x0 + cfg.dt * up, L), x0)
        wp1 = wp0 + cfg.dt * rp
        w1, d2 = deposit(x1, wp1)
        # stage 2 at the predicted state
        u1, r1 = eval_fields(w1)
        up1, d3 = gather(u1, x1)
        rp1, d4 = gather(r1, x1)
        xf = jnp.where(valid[:, None],
                       jnp.mod(x0 + 0.5 * cfg.dt * (up + up1), L), x0)
        wpf = wp0 + 0.5 * cfg.dt * (rp + rp1)
        wf, d5 = deposit(xf, wpf)
        ovf = ovf + d0 + d1 + d2 + d3 + d4 + d5
        return (dataclasses.replace(f, data=wf),
                RT.psum(ovf, (row_axis, col_axis)))

    stepped = RT.shard_map(local_step, mesh,
                           in_specs=(G.field_spec2(row_axis, col_axis),),
                           out_specs=(G.field_spec2(row_axis, col_axis),
                                      P()),
                           check_vma=False)
    return jax.jit(stepped)


def run_distributed(cfg: VortexConfig, n_steps: int, mesh,
                    axis_name="shards", *,
                    auto_reprovision: bool = False,
                    _make_step=None):
    """Distributed driver mirroring :func:`run`: the vorticity field lives
    sharded in a DistributedField for the whole run.

    ``auto_reprovision=True`` adds the control plane: on surfaced halo
    overflow the step is redone from the pre-step field with
    ``mesh_halo`` doubled (clamped to the slab height — the geometric
    ceiling of a single-hop ghost exchange), the :func:`step_reprovision`
    / ``interp_cell_cap`` contract applied to the halo capacity. It costs
    a per-step host sync; the default keeps the accumulate-and-raise path
    so steps dispatch asynchronously. ``_make_step`` is the step factory
    (injectable for testing the control loop without a real overflow)."""
    from repro.core import grid as G
    pencil = (isinstance(axis_name, tuple)
              and int(mesh.shape[axis_name[1]]) > 1)
    make_step = _make_step or make_distributed_vic_step
    step = make_step(mesh, cfg, axis_name)
    w = project_divfree(init_ring(cfg), cfg)
    z0 = float(centroid_z(w, cfg))
    if pencil:
        f = G.distribute_field2(w, mesh, *axis_name)
        n0l = min(cfg.shape[0] // int(mesh.shape[axis_name[0]]),
                  cfg.shape[1] // int(mesh.shape[axis_name[1]]))
    else:
        row = axis_name[0] if isinstance(axis_name, tuple) else axis_name
        f = G.distribute_field(w, mesh, row)
        n0l = cfg.shape[0] // int(mesh.shape[row])
    if auto_reprovision:
        for _ in range(n_steps):
            f2, ovf = step(f)
            while int(ovf) > 0:
                new_halo = min(2 * cfg.mesh_halo, n0l)
                if new_halo == cfg.mesh_halo:
                    raise RuntimeError(
                        f"halo overflow persists at the geometric ceiling "
                        f"mesh_halo={cfg.mesh_halo} (slab height {n0l}); "
                        "the decomposition is too fine for this flow")
                cfg = dataclasses.replace(cfg, mesh_halo=new_halo)
                step = make_step(mesh, cfg, axis_name)
                f2, ovf = step(f)   # redo from the PRE-step field
            f = f2
        return f.data, z0, float(centroid_z(f.data, cfg)), cfg
    # accumulate the overflow on device and sync ONCE after the loop, so
    # steps keep dispatching asynchronously (same rationale as the serial
    # driver's jnp path skipping its per-step host sync)
    total_ovf = jnp.zeros((), jnp.int32)
    for _ in range(n_steps):
        f, ovf = step(f)
        total_ovf = total_ovf + ovf
    if int(total_ovf) != 0:
        raise RuntimeError(
            f"interpolation halo overflow ({int(total_ovf)} deposits/gathers "
            f"outran the halo over {n_steps} steps); raise "
            f"VortexConfig.mesh_halo (= {cfg.mesh_halo})")
    return f.data, z0, float(centroid_z(f.data, cfg))
