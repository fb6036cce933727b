"""Distributed-runtime surface (DESIGN.md §2a).

Every distributed path in this repo — the map()/ghost_get()/ghost_put()
mappings, the grid halo exchange, the MoE token map(), the mamba ghost-state
ring, the launch meshes — goes through this module instead of spelling jax
API names directly. The repo targets one runtime, jax 0.9; concentrating
the distributed surface here keeps a future API rename to this one file.
The policy (which jax APIs are allowed where, and how to add a new
collective) lives in DESIGN.md §2a.

Rules enforced by the test suite (tests/test_system.py checks the grep):

  * ``jax.shard_map`` / ``jax.sharding.AxisType`` are spelled nowhere in
    ``src/`` outside this file.
  * Code running *inside* a shard-mapped function takes collectives from
    this module (``runtime.ppermute`` etc.), never from ``jax.lax``
    directly.
"""
from __future__ import annotations

import os
import pathlib
from typing import Any, Callable, Sequence

import jax
from jax.sharding import AxisType

_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set. Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` (never a temp, per-process or time-stamped
    path), so every later process in the same checkout finds it. Entry
    points call this before their first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def shard_map(fn: Callable, mesh, in_specs, out_specs, *,
              check_vma: bool = False) -> Callable:
    """``jax.shard_map``. The distributed layer always passes
    ``check_vma=False``: the mappings produce replicated outputs via
    explicit pmax/psum, which the checker cannot always prove."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def make_mesh(shape: Sequence[int], names: Sequence[str], *,
              devices: Sequence[Any] | None = None):
    """``jax.make_mesh`` with Auto axes on every dimension.

    jax 0.9 defaults ``make_mesh`` to Explicit axes, under which sharding
    becomes part of each array's type; the distributed layer is written
    for Auto axes (shard_map specs plus compiler-propagated shardings), so
    the mesh says so. ``devices`` selects a subset (e.g. a 4-device
    submesh of 8 forced host devices); default is ``jax.devices()`` prefix
    order."""
    shape = tuple(int(s) for s in shape)
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(shape, tuple(names),
                         axis_types=(AxisType.Auto,) * len(shape), **kwargs)


def device_count() -> int:
    return jax.device_count()


# --------------------------------------------------------------------------
# Collectives used inside shard-mapped functions
# --------------------------------------------------------------------------
# Thin aliases: the per-shard code imports these instead of jax.lax so the
# whole collective surface the repo depends on is enumerated here. Adding a
# collective = adding one alias (plus a line in DESIGN.md §2a).

def axis_index(axis_name: str):
    return jax.lax.axis_index(axis_name)


def axis_size(axis_name: str):
    """Static size of a named mesh axis, from inside a shard-mapped fn (a
    Python int, so it can size Python-level permutation lists)."""
    return jax.lax.axis_size(axis_name)


def ppermute(x, axis_name: str, perm):
    """Collective permute — the ghost_get/ghost_put neighbor shift."""
    return jax.lax.ppermute(x, axis_name, perm)


def all_to_all(x, axis_name: str, *, split_axis: int = 0,
               concat_axis: int = 0, tiled: bool = False):
    """Bucket exchange — the dense rendering of map()'s data exchange."""
    return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=tiled)


def psum(x, axis_name: str):
    return jax.lax.psum(x, axis_name)


def pmax(x, axis_name: str):
    return jax.lax.pmax(x, axis_name)


def pmean(x, axis_name: str):
    return jax.lax.pmean(x, axis_name)


def all_gather(x, axis_name: str, *, axis: int = 0, tiled: bool = False):
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def shift_perms(ndev: int, hop: int = 1):
    """The two ring permutations of a 1-D mesh axis: (right, left) neighbor
    send lists, shared by every slab/ring exchange in the repo. ``hop``
    generalizes to the k-hop rings of the multi-hop ghost exchange
    (DESIGN.md §13): ``hop=1`` (the default) is the classic ±1 shift."""
    right = [(i, (i + hop) % ndev) for i in range(ndev)]
    left = [(i, (i - hop) % ndev) for i in range(ndev)]
    return right, left
