"""Plain reference for one vortex-in-cell step (paper §4.4, Algorithm 1).

Independent of the program: incompressible Navier-Stokes in vorticity form
on a periodic node-centred mesh (node i at i·h, h = L/n). One step:

  1. remesh: a particle on every mesh node carries the node's vorticity;
  2. ψ from ∆ψ = -ω by FFT, with the eigenvalues of the 3-point Laplacian
     and the zero mode set to 0; u = ∇×ψ and RHS = (ω·∇)u + ν∆ω by
     second-order central differences;
  3. M'4 mesh-to-particle interpolation of u and RHS;
  4. two-stage Runge-Kutta (predictor at x + dt·u, midpoint average);
  5. M'4 particle-to-mesh interpolation of the particle vorticity.

Float32 throughout; ``low=True`` rounds the M'4 interpolation's weights
and values to bfloat16 before each product (the sums stay float32), the
lower precision that the correctness check's control runs. The M'4
stencil runs as a loop over its 4^3 offsets
and the node coordinates are built from an iota inside the program, so no
mesh-sized table enters the executable as a constant.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def m4(s):
    s = jnp.abs(s)
    return jnp.where(s < 1.0, 1.0 - 2.5 * s ** 2 + 1.5 * s ** 3,
                     jnp.where(s < 2.0, 0.5 * (2.0 - s) ** 2 * (1.0 - s),
                               0.0))


def _offset(k):
    """k-th (dz, dy, dx) of the 4^3 stencil, each in {-1, 0, 1, 2}."""
    return jnp.stack([k // 16, (k // 4) % 4, k % 4]) - 1


def _base_frac(x, h):
    s = x / h
    base = jnp.floor(s)
    return base.astype(jnp.int32), s - base


def _flat(idx, shape):
    i = [jnp.mod(idx[:, d], shape[d]) for d in range(3)]
    return (i[0] * shape[1] + i[1]) * shape[2] + i[2]


def _round(a, low):
    return a.astype(jnp.bfloat16).astype(a.dtype) if low else a


def m2p(field, x, h, low=False):
    """Gather ``field`` (n0, n1, n2, C) at positions ``x`` (N, 3)."""
    shape = field.shape[:3]
    flat = _round(field.reshape(-1, field.shape[-1]), low)
    base, frac = _base_frac(x, h)

    def body(k, acc):
        off = _offset(k)
        w = _round(jnp.prod(m4(frac - off.astype(x.dtype)), axis=1), low)
        return acc + w[:, None] * flat[_flat(base + off, shape)]

    return jax.lax.fori_loop(0, 64, body,
                             jnp.zeros((x.shape[0], flat.shape[1]),
                                       field.dtype))


def p2m(x, val, h, shape, low=False):
    """Scatter particle values ``val`` (N, C) at ``x`` onto the mesh."""
    base, frac = _base_frac(x, h)
    n = int(np.prod(shape))
    val = _round(val, low)

    def body(k, acc):
        off = _offset(k)
        w = _round(jnp.prod(m4(frac - off.astype(x.dtype)), axis=1), low)
        return acc.at[_flat(base + off, shape)].add(w[:, None] * val)

    out = jax.lax.fori_loop(0, 64, body,
                            jnp.zeros((n, val.shape[1]), val.dtype))
    return out.reshape(tuple(shape) + (val.shape[1],))


def _d(f, axis, h):
    return (jnp.roll(f, -1, axis) - jnp.roll(f, 1, axis)) / (2.0 * h)


def _lap(f, hs):
    return sum((jnp.roll(f, -1, d) - 2.0 * f + jnp.roll(f, 1, d)) / hs[d] ** 2
               for d in range(3))


def poisson(rhs, lengths, lam):
    """Solve ∆u = rhs per component; ``lam`` holds the eigenvalues."""
    rh = jnp.fft.fftn(rhs.astype(jnp.complex64), axes=(0, 1, 2))
    uh = jnp.where(lam[..., None] == 0, 0.0,
                   rh / jnp.where(lam == 0, 1.0, lam)[..., None])
    return jnp.real(jnp.fft.ifftn(uh, axes=(0, 1, 2))).astype(rhs.dtype)


def eigenvalues(shape, lengths):
    """Eigenvalues of the periodic 3-point Laplacian, built in-program."""
    lam = 0.0
    for d, (n, L) in enumerate(zip(shape, lengths)):
        h = L / n
        k = jnp.fft.fftfreq(n, d=h).astype(jnp.float32) * (2 * np.pi)
        e = (2.0 * jnp.cos(k * h) - 2.0) / h ** 2
        lam = lam + e.reshape([n if a == d else 1 for a in range(3)])
    return lam


def velocity(w, hs, lengths, lam):
    psi = poisson(-w, lengths, lam)
    p = [psi[..., c] for c in range(3)]
    return jnp.stack([_d(p[2], 1, hs[1]) - _d(p[1], 2, hs[2]),
                      _d(p[0], 2, hs[2]) - _d(p[2], 0, hs[0]),
                      _d(p[1], 0, hs[0]) - _d(p[0], 1, hs[1])], axis=-1)


def rhs(w, u, hs, nu):
    stretch = sum(w[..., d:d + 1] * _d(u, d, hs[d]) for d in range(3))
    lap = jnp.stack([_lap(w[..., c], hs) for c in range(3)], axis=-1)
    return stretch + nu * lap


@functools.partial(jax.jit, static_argnames=("lengths", "nu", "dt", "low"))
def vic_step(w, *, lengths, nu, dt, low=False):
    """One remeshed RK2 step of the mesh vorticity ``w`` (n0, n1, n2, 3);
    ``low`` runs the M'4 interpolation's products in bfloat16."""
    shape = w.shape[:3]
    hs = [L / n for n, L in zip(shape, lengths)]
    h = jnp.asarray(hs, jnp.float32)
    L = jnp.asarray(lengths, jnp.float32)
    lam = eigenvalues(shape, lengths)
    idx = jax.lax.iota(jnp.int32, int(np.prod(shape)))
    node = jnp.stack([idx // (shape[1] * shape[2]),
                      (idx // shape[2]) % shape[1], idx % shape[2]], axis=1)
    x0 = node.astype(jnp.float32) * h
    wp0 = w.reshape(-1, 3)
    u0 = velocity(w, hs, lengths, lam)
    up = m2p(u0, x0, h, low)
    rp = m2p(rhs(w, u0, hs, nu), x0, h, low)
    x1 = jnp.mod(x0 + dt * up, L)
    w1 = p2m(x1, wp0 + dt * rp, h, shape, low)
    u1 = velocity(w1, hs, lengths, lam)
    up1 = m2p(u1, x1, h, low)
    rp1 = m2p(rhs(w1, u1, hs, nu), x1, h, low)
    xf = jnp.mod(x0 + 0.5 * dt * (up + up1), L)
    return p2m(xf, wp0 + 0.5 * dt * (rp + rp1), h, shape, low)
