"""End-to-end behaviour tests: each paper application reproduces its
headline claim (paper §4 validation criteria)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


def test_md_energy_conservation():
    """§4.1: 'the total energy was conserved' (vs LAMMPS)."""
    from repro.apps import md
    cfg = md.MDConfig(n_per_side=6, dt=0.0005)
    ps, log = md.run(cfg, 40, thermal_v=0.5, log_every=10)
    es = [k + p for _, k, p in log]
    drift = abs(es[-1] - es[0]) / (abs(es[0]) + 1e-9)
    assert np.isfinite(es).all()
    assert drift < 0.05, f"energy drift {drift}"


def test_md_momentum_conservation():
    from repro.apps import md
    cfg = md.MDConfig(n_per_side=6, dt=0.0005)
    ps, _ = md.run(cfg, 25, thermal_v=0.5)
    p = np.asarray(ps.props["v"])[np.asarray(ps.valid)].sum(axis=0)
    assert np.abs(p).max() < 1e-2, p


def test_sph_dam_break_collapses():
    """§4.2: dam-break column collapses and floods rightward."""
    from repro.apps import sph
    cfg = sph.SPHConfig(dp=0.05, box=(1.0, 0.5), fluid=(0.25, 0.25))
    ps = sph.init_dam_break(cfg)
    x0 = float(np.asarray(ps.x)[np.asarray(ps.valid) &
                                (np.asarray(ps.props["kind"]) == 0)][:, 0].max())
    for i in range(400):
        ps, dt, ovf = sph.sph_step(ps, cfg, euler=(i % cfg.verlet_reset == 0))
        assert int(ovf) == 0
    x = np.asarray(ps.x)
    fl = np.asarray(ps.valid) & (np.asarray(ps.props["kind"]) == 0)
    assert np.isfinite(x[fl]).all()
    assert x[fl][:, 0].max() > x0 + 0.05, "no collapse"


def test_gray_scott_pattern_vs_death():
    """§4.3/Fig 6: pattern-forming (F,k) yields structure; death regime
    decays to homogeneous."""
    from repro.apps import gray_scott as GS
    pat = GS.GSConfig(shape=(48, 48), F=0.030, k=0.055, dt=1.0)
    u, v = GS.run(pat, 1500)
    assert GS.pattern_energy(v) > 1e-2, "expected a Turing pattern"
    dead = GS.GSConfig(shape=(48, 48), F=0.010, k=0.070, dt=1.0)
    u2, v2 = GS.run(dead, 1500)
    assert GS.pattern_energy(v2) < GS.pattern_energy(v)


def test_vortex_ring_self_propels():
    """§4.4: the ring advances along its axis (Bergdorf et al. dynamics)."""
    from repro.apps import vortex as V
    cfg = V.VortexConfig(shape=(32, 16, 16), lengths=(8.0, 4.0, 4.0), dt=0.02)
    w, z0, z1 = V.run(cfg, 15)
    assert np.isfinite(float(V.enstrophy(w)))
    assert z1 > z0 + 0.01, (z0, z1)


def test_dem_avalanche_flows():
    """§4.5: grains flow downslope on a 30° incline; nothing penetrates
    the floor; Coulomb bound respected by construction."""
    from repro.apps import dem
    cfg = dem.DEMConfig(box=(2.0, 0.6, 1.0), fill=(0.8, 0.66, 0.5))
    ps = dem.init_block(cfg)
    for i in range(250):
        ps, flags = dem.dem_step(ps, cfg)
        assert int(flags.any()) == 0
    v = np.asarray(ps.props["v"])[np.asarray(ps.valid)]
    x = np.asarray(ps.x)[np.asarray(ps.valid)]
    assert np.isfinite(v).all()
    assert v[:, 0].mean() > 0.0, "avalanche should flow in +x"
    assert (x[:, 2] > -0.05).all(), "floor penetration"


def test_runtime_compatibility_policy():
    """DESIGN.md §2a: the jax distributed API names (``jax.shard_map``,
    ``AxisType``) may be spelled only inside core/runtime.py — everything
    else goes through it, so an API change touches one file."""
    import os
    import re
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    shim = os.path.join("core", "runtime.py")
    offenders = []
    pat = re.compile(r"jax\.shard_map|AxisType")
    for dirpath, _, files in os.walk(src):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            if os.path.normpath(path).endswith(
                    os.path.join("repro", shim)):
                continue
            with open(path) as fh:
                for lineno, line in enumerate(fh, 1):
                    if pat.search(line):
                        offenders.append(f"{path}:{lineno}: {line.strip()}")
    assert not offenders, \
        "version-gated jax API outside core/runtime.py:\n" + \
        "\n".join(offenders)


def test_ps_cmaes_beats_independent():
    """§4.6: swarm coupling outperforms independent CMA-ES instances on a
    multimodal function (success-performance criterion, fixed eval budget —
    deterministic seed, budget long enough for migration to matter)."""
    from repro.apps import cmaes
    bf_s, _, _ = cmaes.ps_cma_es(cmaes.rastrigin, 10, 4, 20000, seed=3,
                                 swarm=True)
    bf_i, _, _ = cmaes.ps_cma_es(cmaes.rastrigin, 10, 4, 20000, seed=3,
                                 swarm=False)
    assert np.isfinite(bf_s) and np.isfinite(bf_i)
    assert bf_s <= bf_i + 1e-9, (bf_s, bf_i)
    # and CMA-ES itself converges on a convex function
    sphere = lambda x: np.sum((x - 1.23) ** 2, axis=-1)
    bf, _, _ = cmaes.ps_cma_es(sphere, 8, 2, 5000, seed=1, swarm=False)
    assert bf < 1e-8
