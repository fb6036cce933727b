"""The cell list ranks each particle within its cell from the start of its
run of equal keys in the sorted order. These tests hold it, bit for bit, to
the binary-search formulation it replaced (kept here as the reference), and
keep a search loop from coming back into the layer."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import vortex as V
from repro.core import cell_list as CL, particles as P, remesh as RM
from repro.kernels.m4_interp import ops as M4


@partial(jax.jit, static_argnames=("cell_cap", "grid_shape", "box_lo",
                                   "box_hi"))
def _searchsorted_cells(ps, *, box_lo, box_hi, grid_shape, cell_cap):
    """The rank by binary search over the sorted keys: the reference."""
    cap = ps.capacity
    n_cells = int(np.prod(grid_shape))
    cell_id = CL._flat_cell_of(ps.x, ps.valid, box_lo, box_hi, grid_shape)
    order = jnp.argsort(cell_id, stable=True).astype(jnp.int32)
    sorted_cells = cell_id[order]
    start = jnp.searchsorted(sorted_cells, sorted_cells, side="left")
    rank = jnp.arange(cap, dtype=jnp.int32) - start.astype(jnp.int32)
    cells = jnp.full((n_cells + 1, cell_cap), cap, jnp.int32)
    cells = cells.at[sorted_cells, rank].set(order, mode="drop")
    counts = jnp.bincount(cell_id, length=n_cells + 1).astype(jnp.int32)
    fill = jnp.max(counts[:n_cells])
    return dict(cells=cells, counts=counts, cell_id=cell_id, fill=fill,
                overflow=jnp.maximum(fill - cell_cap, 0))


def _random(seed, n, dim, invalid_share=0.1):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.uniform(0.0, 1.0, (n, dim)), jnp.float32)
    ps = P.from_positions(x, capacity=n + n // 8)
    keep = jnp.asarray(rng.uniform(size=ps.capacity) >= invalid_share)
    return ps.where(keep)


def _case(name):
    """(ParticleSet, grid_shape, cell_cap) of each case, on the unit box."""
    if name == "random_invalid":
        return _random(0, 3000, 3), (6, 6, 6), 32
    if name == "one_cell_overflow":
        x = jnp.full((200, 3), 0.31, jnp.float32)
        return P.from_positions(x, capacity=256), (4, 4, 4), 48
    if name == "capacity_one":
        return _random(1, 1000, 3, 0.0), (5, 5, 5), 1
    if name == "one_cell_occupied":
        x = jnp.asarray(np.random.default_rng(2).uniform(0.76, 0.99, (40, 3)),
                        jnp.float32)
        return P.from_positions(x, capacity=64), (4, 4, 4), 16
    if name == "grid_2d":
        return _random(3, 2000, 2), (9, 7), 24
    raise KeyError(name)


@pytest.mark.parametrize("case", ["random_invalid", "one_cell_overflow",
                                  "capacity_one", "one_cell_occupied",
                                  "grid_2d", "m4_ring_16x8x8"])
def test_cell_list_matches_searchsorted_rank(case):
    if case == "m4_ring_16x8x8":
        cfg = V.VortexConfig(shape=(16, 8, 8), use_pallas=True)
        kw = dict(box_lo=(0.0, 0.0, 0.0), box_hi=cfg.lengths,
                  periodic=(True, True, True))
        ps, _ = RM.seed_from_mesh(V.init_ring(cfg), threshold=1e-3, dim=3,
                                  capacity=16 * 8 * 8, **kw)
        # one advection-sized displacement off the nodes, wrapped
        rng = np.random.default_rng(4)
        L = jnp.asarray(cfg.lengths, jnp.float32)
        x = jnp.mod(ps.x + jnp.asarray(
            rng.normal(0.0, 0.1, ps.x.shape), jnp.float32), L)
        x = jnp.where(ps.valid[:, None], x, ps.x)
        got = M4.bucket_particles(x, ps.valid, shape=cfg.shape, **kw)
        masked = P.ParticleSet(
            x=jnp.where(ps.valid[:, None], x, P.ParticleSet.FILL),
            props={}, valid=ps.valid)
        grid = (4, 2, 2)
        ref = _searchsorted_cells(masked, box_lo=kw["box_lo"],
                                  box_hi=kw["box_hi"], grid_shape=grid,
                                  cell_cap=M4.default_cell_cap(4, 3))
        rows = ref["cells"][:int(np.prod(grid))]
        assert int(jnp.sum(ps.valid)) < ps.capacity
        np.testing.assert_array_equal(got.safe,
                                      jnp.minimum(rows, ps.capacity - 1))
        np.testing.assert_array_equal(got.cell_mask, rows < ps.capacity)
        return
    ps, grid, cell_cap = _case(case)
    dim = len(grid)
    box = dict(box_lo=(0.0,) * dim, box_hi=(1.0,) * dim, grid_shape=grid)
    cl = CL.build_cell_list(ps, periodic=(True,) * dim, cell_cap=cell_cap,
                            **box)
    ref = _searchsorted_cells(ps, cell_cap=cell_cap, **box)
    for field in ("cells", "counts", "cell_id", "fill", "overflow"):
        np.testing.assert_array_equal(getattr(cl, field), ref[field],
                                      err_msg=field)
    if case == "one_cell_overflow":
        assert int(cl.overflow) == 200 - 48
    if case == "one_cell_occupied":
        assert int(jnp.sum(cl.counts[:-1] > 0)) == 1


def test_cell_list_has_no_search_loop():
    """No ``while`` in the lowered build: a binary search (jnp.searchsorted
    lowers to a loop of gathers) costs ~20 full-length passes a build."""
    ps, grid, cell_cap = _case("random_invalid")
    box = dict(box_lo=(0.0,) * 3, box_hi=(1.0,) * 3, grid_shape=grid,
               cell_cap=cell_cap)
    assert "while" in _searchsorted_cells.lower(ps, **box).as_text()
    text = jax.jit(CL.build_cell_list, static_argnames=(
        "cell_cap", "grid_shape", "periodic", "box_lo", "box_hi")).lower(
            ps, periodic=(True,) * 3, **box).as_text()
    assert "while" not in text
