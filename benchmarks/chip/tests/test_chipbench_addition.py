"""A configuration and its cell join the benchmark through new data files
and list entries in ``BENCHMARK.json`` alone: in a copy of the benchmark's
data, a new configuration, traffic mix, workload and tiny size validate,
the new cell rehearses ``correct`` and the cells already there still do;
a configuration with no tiny file leaves the other cells' rehearsal as it
was."""
import json
import shutil

import pytest

import chipbench_tiny as T

MF = T.run.MF
DATA = ("configs", "workloads", "traffic", "tests/tiny")


def _source(root, config, cfg, traffic, workload, tiny=None):
    """A tree with the benchmark's manifest and data files, to which the
    configuration ``config`` (file contents ``cfg``) and its cell
    ``workload`` (name, traffic name and contents, file contents) are
    added as new files and list entries."""
    man = json.loads((T.ROOT / "BENCHMARK.json").read_text())
    bench = root / man["paths"][0]
    for sub in DATA:
        shutil.copytree(T.BENCH / sub, bench / sub)
    cell, traffic_name = workload["name"], traffic["name"]
    man["configs"].append({
        "name": config, "source": "https://arxiv.org/abs/1804.07598",
        "file": f"{man['paths'][0]}/configs/{config}.json", "reduced": [],
        "why": "a configuration added by data files alone"})
    man["workloads"].append({
        "name": cell, "config": config, "traffic": traffic_name, "chips": 1,
        "why": "a cell added by data files alone"})
    for m in man["end_to_end"] + man["per_layer"]:
        if m["name"] in ("particle_steps_per_s", "device_idle_share.md"):
            m["workloads"].append(cell)
    (bench / "configs" / f"{config}.json").write_text(json.dumps(cfg))
    (bench / "workloads" / f"{cell}.json").write_text(
        json.dumps(workload["file"]))
    path = bench / "traffic" / f"{traffic_name}.json"
    if not path.exists():
        path.write_text(json.dumps(traffic["file"]))
    if tiny is not None:
        (bench / "tests" / "tiny" / f"{config}.json").write_text(
            json.dumps(tiny))
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


def _md_cell(name, limits_from="md216k_1chip"):
    limits = json.loads((T.BENCH / "workloads" /
                         f"{limits_from}.json").read_text())["limits"]
    return {"name": name,
            "file": {"chips": 1, "mesh": None, "limits": limits}}


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """The benchmark with an MD configuration of 20^3 particles under a
    warmer lattice start, its tiny size 8^3."""
    base = tmp_path_factory.mktemp("added")
    cfg = json.loads((T.BENCH / "configs" / "md_lj_216k.json").read_text())
    cfg.update(n_per_side=20, box=2.0)
    src = _source(base / "src", "md_lj_8k_warm", cfg,
                  {"name": "lattice_warm",
                   "file": {"start": "simple-cubic lattice, warmer",
                            "thermal_v": 0.5, "warmup_steps": 2}},
                  _md_cell("md8k_warm"),
                  tiny={"n_per_side": 8, "box": 0.8})
    return src, T.write_tiny(base / "tiny", src=src)


def test_added_configuration_validates(added):
    src, _ = added
    man = MF.load(src)
    assert MF.validate(man, src) == []
    cell = MF.Cell(man, src, "md8k_warm")
    assert {m["name"] for m in cell.per_layer} == {"device_idle_share.md"}
    assert {m["name"] for m in cell.end_to_end} == {
        "particle_steps_per_s", "setup_s"}


@pytest.mark.parametrize("cell", ["md8k_warm", "md216k_1chip",
                                  "vic256_1chip"])
def test_cells_rehearse_beside_an_added_one(added, cell):
    _, root = added
    if cell == "md8k_warm":
        cfg = MF.Cell(MF.load(root), root, cell).config
        assert (cfg["n_per_side"], cfg["box"]) == (8, 0.8)
    res = T.run_tiny(root, cell)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0


def test_configuration_without_tiny_file(tmp_path):
    """Copied at its own size, and run by no test that does not name it;
    the cells beside it rehearse as before."""
    cfg = json.loads((T.BENCH / "configs" / "md_lj_216k.json").read_text())
    src = _source(tmp_path / "src", "md_lj_216k_copy", cfg,
                  {"name": "lattice_thermal"}, _md_cell("md216k_copy"))
    assert MF.validate(MF.load(src), src) == []
    root = T.write_tiny(tmp_path / "tiny", src=src)
    man = MF.load(root)
    assert MF.Cell(man, root, "md216k_copy").config == cfg
    res = T.run_tiny(root, "md216k_1chip")
    assert res["correct"] is True, res["checks"]
