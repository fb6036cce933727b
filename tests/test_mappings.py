"""Distributed mapping tests (paper §3.4).

Two layers:

  * Single-device property tests for the pure packing/routing helpers
    (``bucket_pack``) — run in-process, hypothesis where available plus a
    seeded randomized sweep that always runs.
  * The multi-device suite — real pytest files under tests/distributed/
    (opt-in, 8 forced host devices), launched through the single subprocess
    entry point in tests/_dist_launcher.py. These run on every supported
    jax version via core/runtime.py; there is no version gate.
"""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from _dist_launcher import run_distributed_pytest
from repro.core import mappings as M


# --------------------------------------------------------------------------
# bucket_pack properties (single device)
# --------------------------------------------------------------------------

def _check_bucket_pack(dest_np: np.ndarray, ndev: int, cap: int) -> None:
    """The bucket_pack contract: for each destination d < ndev, the valid
    slots of bucket d hold exactly the first min(count_d, cap) particles
    with dest==d (stable original order), each exactly once; dest >= ndev
    is discarded; overflow == max(0, max_d count_d - cap) exactly, and
    fill == max_d count_d."""
    n = len(dest_np)
    ids = np.arange(n, dtype=np.int32)
    buckets, slot_valid, overflow, fill = M.bucket_pack(
        jnp.asarray(dest_np), {"id": jnp.asarray(ids)}, ndev, cap)
    bid = np.asarray(buckets["id"])
    sv = np.asarray(slot_valid)
    assert bid.shape == (ndev, cap) and sv.shape == (ndev, cap)

    in_range = dest_np < ndev
    counts = np.bincount(dest_np[in_range], minlength=ndev)
    max_count = int(counts.max()) if ndev > 0 and counts.size else 0
    assert int(overflow) == max(0, max_count - cap), \
        (int(overflow), max_count, cap)
    assert int(fill) == max_count, (int(fill), max_count)

    for d in range(ndev):
        sent = ids[dest_np == d]          # stable original order
        kept = sent[:cap]
        got = bid[d][sv[d]]
        assert sorted(got.tolist()) == sorted(kept.tolist()), \
            (d, got, kept)

    # global: no particle lands twice (across all buckets and slots)
    all_got = bid[sv]
    assert len(np.unique(all_got)) == len(all_got), "duplicated particle"
    if int(overflow) == 0:
        assert len(all_got) == int(in_range.sum()), "lost particle"


@settings(max_examples=60, deadline=None)
@given(st.data())
def test_bucket_pack_property(data):
    """Hypothesis sweep over random dest distributions and capacities."""
    ndev = data.draw(st.integers(min_value=1, max_value=8), label="ndev")
    n = data.draw(st.integers(min_value=1, max_value=120), label="n")
    cap = data.draw(st.integers(min_value=1, max_value=40), label="cap")
    dest = np.asarray(
        data.draw(st.lists(st.integers(min_value=0, max_value=ndev + 2),
                           min_size=n, max_size=n), label="dest"),
        np.int32)
    _check_bucket_pack(dest, ndev, cap)


def test_bucket_pack_randomized_cases():
    """Seeded randomized sweep (runs even without hypothesis installed)."""
    rng = np.random.default_rng(0)
    for _ in range(40):
        ndev = int(rng.integers(1, 9))
        n = int(rng.integers(1, 150))
        cap = int(rng.integers(1, 41))
        dest = rng.integers(0, ndev + 3, size=n).astype(np.int32)
        _check_bucket_pack(dest, ndev, cap)


def test_bucket_pack_edge_cases():
    # heavy skew: everyone to one destination, overflow exact
    _check_bucket_pack(np.zeros(50, np.int32), 4, 8)
    # everything discarded (dest >= ndev): empty buckets, zero overflow
    _check_bucket_pack(np.full(20, 7, np.int32), 4, 8)
    # exactly at capacity: no overflow, nothing lost
    _check_bucket_pack(np.repeat(np.arange(4, dtype=np.int32), 8), 4, 8)


# --------------------------------------------------------------------------
# Multi-device suite launchers (one subprocess entry point, real pytest
# files — see tests/distributed/). Must pass on every supported jax.
# --------------------------------------------------------------------------

@pytest.mark.distributed
def test_mappings_distributed_8dev():
    """map()/ghost_get()/ghost_put() on a real 8-device mesh, including the
    sum/max/min merge-op round trips against the scatter-reduce oracle."""
    run_distributed_pytest("tests/distributed/test_dist_mappings.py",
                           min_passed=6)


@pytest.mark.distributed
def test_distributed_grid_halo_exchange():
    run_distributed_pytest(
        "tests/distributed/test_dist_equivalence.py"
        "::test_grid_halo_stencil_matches_serial")


@pytest.mark.distributed
def test_distributed_md_matches_serial():
    """The paper's full pattern — map() + ghost_get() + local compute —
    reproduces the serial trajectory particle-for-particle."""
    run_distributed_pytest(
        "tests/distributed/test_dist_equivalence.py"
        "::test_md_distributed_matches_serial")


@pytest.mark.distributed
def test_distributed_equivalence_sph_and_gray_scott():
    """Serial-vs-distributed equivalence for the SPH dam break and the
    Gray-Scott app driver (≤1e-4 on 8 forced host devices)."""
    run_distributed_pytest(
        "tests/distributed/test_dist_equivalence.py"
        "::test_sph_distributed_matches_serial",
        "tests/distributed/test_dist_equivalence.py"
        "::test_gray_scott_distributed_matches_serial",
        min_passed=2)


@pytest.mark.distributed
def test_distributed_equivalence_dem_and_vortex():
    """The simulation layer's free wins: distributed DEM (id-keyed
    tangential history over map()/ghost_get) and the sharded-particle
    vortex remeshing step, each ≤1e-4 against the serial engine."""
    run_distributed_pytest(
        "tests/distributed/test_dist_equivalence.py"
        "::test_dem_distributed_matches_serial",
        "tests/distributed/test_dist_equivalence.py"
        "::test_vortex_distributed_matches_serial",
        min_passed=2)


@pytest.mark.distributed
def test_distributed_mesh_field_layer():
    """The distributed mesh layer (DESIGN.md §10): halo_pad vs numpy
    oracles (incl. non-periodic edge replication), the ghost_put
    halo-reduce P2M vs the full-psum deposit, the slab-decomposed FFT
    Poisson vs the serial solver, and mesh fields riding make_sim_step."""
    run_distributed_pytest("tests/distributed/test_dist_field.py",
                           min_passed=11)


@pytest.mark.distributed
def test_distributed_overflow_flags():
    """bucket_cap / ghost_cap / cell-list / ghost-contract / contact-slot
    overflow surfacing through make_sim_step for all three pair apps."""
    run_distributed_pytest("tests/distributed/test_dist_overflow.py",
                           min_passed=11)


@pytest.mark.distributed
def test_distributed_fleet_and_cmaes():
    """Fleet batch axis sharded over 8 devices: batched-vs-loop
    equivalence, server churn against one compiled step, and the sharded
    PS-CMA-ES population matching its single-device run."""
    run_distributed_pytest("tests/distributed/test_dist_fleet.py",
                           min_passed=3)


@pytest.mark.distributed
@pytest.mark.slow
def test_distributed_sph_with_dlb():
    """Paper Table 3 showcase: dam break under DLB — SAR triggers
    rebalances and the fluid stays consistent (no overflow, finite)."""
    run_distributed_pytest("tests/distributed/test_dist_sph_dlb.py",
                           timeout=1200)


@pytest.mark.distributed
@pytest.mark.slow
def test_distributed_reuse_engine():
    """Skin-amortized ghost reuse (DESIGN.md §14): reuse="skin" trajectory
    equivalence for MD (overlap on/off) and SPH, the skin/2 no-missed-pairs
    oracle (serial ≡ 8-device, with reuse="update" as the tripwire-off
    negative control), DEM contact-cache carry/re-pin across update steps,
    the inert 2-D fallback, and the pinned 2-D NotImplementedError
    contracts."""
    run_distributed_pytest("tests/distributed/test_dist_reuse.py",
                           timeout=1500, min_passed=9)
