"""Compile the main-path Pallas kernels for a TPU v5e chip at deployment
widths, without a chip: the TPU compiler is installed and compiles for a
described topology. Interpret mode (every other kernel test) cannot show
that Mosaic accepts a kernel's block shapes and slices; these tests can.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, so each test worker
that does not run this file must not touch it.
"""
import os

import jax
import jax.numpy as jnp
import pytest

# MD deployment widths (paper §4.1: 60^3 particles, box 6, r_cut 0.255)
C_MD, CC_MD, K3 = 23 ** 3, 48, 27
VIC_SHAPE, VIC_CB = (256, 64, 64), 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _shape(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("body", ["lj", "sph"])
def test_cell_pair_compiles_for_v5e(one_chip, body):
    from repro.apps import md, sph
    from repro.kernels.cell_pair.cell_pair import cell_pair_pallas
    s = lambda *shape, dtype=jnp.float32: _shape(one_chip, shape, dtype)
    tiles = (s(C_MD, CC_MD, 3), s(C_MD, K3 * CC_MD, 3),
             s(C_MD, CC_MD, dtype=jnp.bool_),
             s(C_MD, K3 * CC_MD, dtype=jnp.bool_))
    if body == "lj":
        fn = lambda a, b, c, d: cell_pair_pallas(
            a, b, c, d, body=md.lj_pair_body(0.085, 1.0),
            out={"f": "radial"}, r_cut=0.255)
        _assert_mosaic(fn, *tiles)
        return
    cfg = sph.SPHConfig(dim=3, dp=0.02, box=(1.6, 0.67, 0.4),
                        fluid=(0.4, 0.67, 0.3))
    props_i = {"v": s(C_MD, CC_MD, 3), "rho": s(C_MD, CC_MD)}
    props_j = {"v": s(C_MD, K3 * CC_MD, 3), "rho": s(C_MD, K3 * CC_MD)}
    fn = lambda a, b, c, d, pi, pj: cell_pair_pallas(
        a, b, c, d, pi, pj, body=sph.sph_pair_body(cfg),
        out={"a": "radial", "drho": "scalar"}, r_cut=cfg.r_cut)
    _assert_mosaic(fn, *tiles, props_i, props_j)


def _m4_kw():
    grid = tuple(n // VIC_CB for n in VIC_SHAPE)
    return grid, dict(grid_cells=grid, cb=VIC_CB, box_lo=(0.0, 0.0, 0.0),
                      box_hi=(22.0, 5.57, 5.57))


def test_p2m_cells_compiles_for_v5e(one_chip):
    from repro.kernels.m4_interp.m4_interp import p2m_cells
    from repro.kernels.m4_interp.ops import default_cell_cap
    grid, kw = _m4_kw()
    n_cells, cc = grid[0] * grid[1] * grid[2], default_cell_cap(VIC_CB, 3)
    _assert_mosaic(lambda x, v, m: p2m_cells(x, v, m, **kw),
                   _shape(one_chip, (n_cells, cc, 3)),
                   _shape(one_chip, (n_cells, cc, 3)),
                   _shape(one_chip, (n_cells, cc), jnp.bool_))


def test_m2p_cells_compiles_for_v5e(one_chip):
    from repro.kernels.m4_interp.m4_interp import m2p_cells
    from repro.kernels.m4_interp.ops import default_cell_cap
    grid, kw = _m4_kw()
    n_cells, cc = grid[0] * grid[1] * grid[2], default_cell_cap(VIC_CB, 3)
    # VIC's fused M2P stacks u and the RHS: 6 channels
    _assert_mosaic(lambda f, x, m: m2p_cells(f, x, m, **kw),
                   _shape(one_chip, VIC_SHAPE + (6,)),
                   _shape(one_chip, (n_cells, cc, 3)),
                   _shape(one_chip, (n_cells, cc), jnp.bool_))
