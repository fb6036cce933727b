"""The MD driver on the reuse engine, through a cell added as data files
(``md216k_skin``: ``md_lj_216k`` with ``step`` {reuse: skin}, in a copy of
the benchmark's data) at its tiny size, 12^3 particles in a box of 1.2: it
rehearses ``correct``, its bfloat16 control and the faults do not, the
driver steps a ``ReuseState`` and reads results from its inner particle
set, and a step down the rebuild path passes the same check."""
import json

import jax
import pytest

import chipbench_tiny as T
from test_chipbench_addition import _source
from test_chipbench_faults import Altered, NoDrift, Unchanged

MF = T.run.MF
CELL = "md216k_skin"


def skin_tree(base, over=None):
    """A checkout-like tiny tree holding the benchmark's cells and the
    reuse cell, which joins as new files and list entries only."""
    cfg = json.loads((T.BENCH / "configs" / "md_lj_216k.json").read_text())
    limits = json.loads((T.BENCH / "workloads" /
                         "md216k_1chip.json").read_text())["limits"]
    src = _source(base / "src", "md_lj_216k_skin", cfg,
                  {"name": "lattice_melt",
                   "file": {"start": "simple-cubic lattice, past its collapse",
                            "thermal_v": 0.3, "warmup_steps": 300}},
                  {"name": CELL,
                   "file": {"chips": 1, "mesh": None, "limits": limits,
                            "step": {"reuse": "skin", "skin": 0.0255}}},
                  tiny={"n_per_side": 12, "box": 1.2})
    return T.write_tiny(base / "tiny", over=over, src=src)


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = skin_tree(tmp_path_factory.mktemp("skin"))
    return MF.Cell(MF.load(root), root, CELL)


def _session(cell, step=None):
    workload = dict(cell.workload)
    if step is not None:
        workload["step"] = step
    driver = T.run.load_module("drivers", cell.config["app"])
    return driver.setup(cell.config, cell.traffic, workload,
                        T.run.seed32(2 ** 40 + 11), jax.devices()[:1])


def test_skin_rehearsal(tmp_path):
    res = T.run_tiny(skin_tree(tmp_path), CELL)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"particle_steps_per_s", "setup_s"}


def test_skin_lower_precision_control_is_not_correct(tmp_path):
    res = T.run_tiny(skin_tree(tmp_path, over={"precision": "bf16x"}), CELL)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("fault", [Unchanged, Altered, NoDrift])
def test_skin_fault_is_not_correct(tmp_path, fault):
    res = T.run_tiny(skin_tree(tmp_path), CELL,
                     wrap=lambda s: fault(s, "state"))
    assert res["correct"] is False, res["checks"]


def test_skin_snapshot_is_the_inner_particle_set(cell):
    sess = _session(cell)
    assert type(sess.state).__name__ == "ReuseState"
    assert bool(sess.state.cache.ok)          # warm-up built the cache
    x, v, f, valid, _ = sess.snapshot()
    ps = sess.state.inner.ps
    assert x is ps.x and v is ps.props["v"] and f is ps.props["f"]
    assert valid is ps.valid


def test_rebuild_steps_are_correct(cell):
    """With a skin so thin that every step trips the wire, each step takes
    the full path and matches the reference."""
    sess = _session(cell, {"reuse": "skin", "skin": 1e-6})
    prev = sess.snapshot()
    samples = []
    for _ in range(3):
        assert sess.step() == 0
        cur = sess.snapshot()
        samples.append((prev, cur))
        prev = cur
    checks = sess.check(samples)
    assert all(v <= lim for _, v, lim in checks), checks
