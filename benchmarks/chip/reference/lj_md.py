"""Plain reference for one Lennard-Jones velocity-Verlet step.

Independent of the program: a host-built cell binning (numpy) places each
particle in one of n^3 cells no narrower than r_cut, and every particle
sums the forces of every particle in its 27 surrounding cells, with the
minimum-image displacement, in float32 on the device. The step is the
textbook velocity Verlet of the paper's Listing 4.1 with unit mass: half
kick with the forces at the input positions (recomputed here, never taken
from the program), drift, periodic wrap, forces at the new positions,
second half kick.

The cells sit on a grid padded by one periodic image cell on each side and
flattened, so a neighbour cell at a fixed offset is a fixed shift of the
flat index: each of the 27 offsets is a static slice, and no particle is
gathered one by one (the chip gathers single elements slowly).
"""
from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

SLOTS = 32      # slots a reference cell holds, or the next multiple of 8


def _binning(x: np.ndarray, box: float, r_cut: float):
    """(m, table, home): the padded grid's side m = n + 2 for n cells of
    r_cut or more per axis; the particle held in each slot of each padded
    cell, (slots, m^3) padded with -1, image cells holding copies of the
    cells they wrap to; which padded cells are real (not images). A cell
    has SLOTS slots, or more where a cell holds more particles."""
    n = int(np.floor(box / r_cut))
    if n < 3:
        raise ValueError(f"box {box} holds {n} < 3 cells of r_cut {r_cut}")
    m = n + 2
    c3 = np.clip(np.floor(x / (box / n)).astype(np.int64), 0, n - 1) + 1
    cell = (c3[:, 0] * m + c3[:, 1]) * m + c3[:, 2]
    order = np.argsort(cell, kind="stable")
    counts = np.bincount(cell, minlength=m ** 3)
    slots = max(SLOTS, -(-int(counts.max()) // 8) * 8)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    rank = np.arange(len(x)) - starts[cell[order]]
    table = np.full((m ** 3, slots), -1, np.int32)
    table[cell[order], rank] = order
    wrap = (np.arange(m) - 1) % n + 1            # padded row -> real row
    a, b, c = np.meshgrid(wrap, wrap, wrap, indexing="ij")
    table = table[((a * m + b) * m + c).reshape(-1)]
    real = np.zeros((m, m, m), bool)
    real[1:-1, 1:-1, 1:-1] = True
    return m, np.ascontiguousarray(table.T), real.reshape(-1)


@functools.partial(jax.jit, static_argnames=("m", "box", "sigma", "epsilon",
                                             "r_cut"))
def _cell_forces(p, occupied, home, *, m, box, sigma, epsilon, r_cut):
    """(3, S, L) forces on the particles of the padded cells [D, D + L),
    D = m^2 + m + 1 (every real cell lies there), from every particle in
    the 27 surrounding cells. ``p`` (3, S, m^3) positions, ``occupied``
    (S, m^3), ``home`` (S, m^3) the slots whose forces are wanted."""
    slots = p.shape[1]
    d0 = m * m + m + 1
    length = m ** 3 - 2 * d0
    own = [p[d][:, d0:d0 + length] for d in range(3)]
    want = home[:, d0:d0 + length]
    not_self = ~jnp.eye(slots, dtype=bool)[:, :, None]
    f = [jnp.zeros((slots, length), jnp.float32) for _ in range(3)]
    for ox, oy, oz in itertools.product((-1, 0, 1), repeat=3):
        s = d0 + (ox * m + oy) * m + oz
        ok = want[:, None, :] & occupied[None, :, s:s + length]
        if (ox, oy, oz) == (0, 0, 0):
            ok = ok & not_self
        dx = []
        for d in range(3):
            e = own[d][:, None, :] - p[d][None, :, s:s + length]
            dx.append(e - box * jnp.round(e / box))
        r2 = dx[0] * dx[0] + dx[1] * dx[1] + dx[2] * dx[2]
        ok = ok & (r2 < r_cut * r_cut)
        r2s = jnp.where(ok, r2, 1.0)
        inv3 = (sigma * sigma / r2s) ** 3
        mag = jnp.where(ok, 24.0 * epsilon * (2.0 * inv3 * inv3 - inv3) / r2s,
                        0.0)
        for d in range(3):
            f[d] = f[d] + jnp.sum(mag * dx[d], axis=1)
    return jnp.stack(f)


def lj_forces(x, *, box, sigma, epsilon, r_cut):
    """(N, 3) float32 forces on the device for positions ``x`` (N, 3)."""
    xh = np.asarray(x, np.float32)
    m, table, real = _binning(xh, box, r_cut)
    occupied = table >= 0
    home = occupied & real[None, :]
    p = np.where(occupied[None], xh[np.maximum(table, 0)].transpose(2, 0, 1),
                 np.float32(0.0))
    fc = _cell_forces(jnp.asarray(p), jnp.asarray(occupied),
                      jnp.asarray(home), m=m, box=box, sigma=sigma,
                      epsilon=epsilon, r_cut=r_cut)
    d0 = m * m + m + 1
    sl = slice(d0, d0 + fc.shape[2])
    rows, cols = np.nonzero(home[:, sl])
    ids = table[:, sl][rows, cols]
    out = np.zeros((len(xh), 3), np.float32)
    out[ids] = np.asarray(fc)[:, rows, cols].T
    return jnp.asarray(out)


def verlet_step(x, v, *, box, sigma, epsilon, r_cut, dt):
    """One velocity-Verlet step from (x, v); returns (x1, v1, f1) as numpy
    float32 arrays."""
    kw = dict(box=box, sigma=sigma, epsilon=epsilon, r_cut=r_cut)
    x = jnp.asarray(x, jnp.float32)
    v = jnp.asarray(v, jnp.float32)
    f0 = lj_forces(x, **kw)
    vh = v + 0.5 * dt * f0
    x1 = jnp.mod(x + dt * vh, box)
    f1 = lj_forces(x1, **kw)
    v1 = vh + 0.5 * dt * f1
    return tuple(np.asarray(a) for a in (x1, v1, f1))
