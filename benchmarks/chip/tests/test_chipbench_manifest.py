"""BENCHMARK.json and the benchmark's data files: names, units, cross
references, the share of four-chip cells, and a reader for every metric."""
import copy
import json

import pytest

import chipbench_tiny as T

MF = T.run.MF


@pytest.fixture()
def man():
    return json.loads((T.ROOT / "BENCHMARK.json").read_text())


def test_manifest_is_sound(man):
    assert MF.validate(man, T.ROOT) == []
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert [w["name"] for w in man["workloads"]][:2] == [
        "md216k_1chip", "vic256_1chip"]


def test_every_metric_has_a_reader(man):
    for m in man["end_to_end"] + man["per_layer"]:
        assert (T.BENCH / "metrics" / f"{m['name']}.py").is_file(), m


def test_every_cell_loads(man):
    for w in man["workloads"]:
        cell = MF.Cell(man, T.ROOT, w["name"])
        assert (T.BENCH / "drivers" / f"{cell.config['app']}.py").is_file()
        assert cell.workload["limits"]
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer


def _broken(man, how):
    m = copy.deepcopy(man)
    if how == "unit":
        m["end_to_end"][0]["unit"] = "particle steps per s"
    elif how == "name":
        m["workloads"][0]["name"] = "md 216k"
    elif how == "config":
        m["workloads"][0]["config"] = "nope"
    elif how == "moves":
        m["per_layer"][0]["workloads"] = ["vic256_1chip"]
    elif how == "four_chips":
        for w in m["workloads"]:
            w["chips"] = 4
    elif how == "bound":
        m["end_to_end"][0]["bound"] = 0.5
    elif how == "no_workloads":
        del m["per_layer"][0]["workloads"]
    return m


@pytest.mark.parametrize("how", ["unit", "name", "config", "moves",
                                 "four_chips", "bound", "no_workloads"])
def test_validation_catches(man, how):
    assert MF.validate(_broken(man, how))


MD_LAYERS = {"device_idle_share.md", "engine_xla_ms.md", "pair_kernel_ms.md",
             "pair_kernel_roofline.md", "cell_list_ms.md",
             "candidate_gather_ms.md", "slot_scatter_ms.md", "exchange_ms.md"}


@pytest.mark.parametrize("cell, metrics", [
    ("md216k_1chip", MD_LAYERS),
    ("vic256_1chip", {"device_idle_share.vic", "m4_kernel_ms.vic",
                      "m4_kernel_roofline.vic", "fft_ms.vic",
                      "bucketing_ms.vic", "m4_unbucket_ms.vic"}),
    ("md857k_slab4", MD_LAYERS | {"collective_ms.md"}),
])
def test_cells_keep_their_per_layer_metrics(man, cell, metrics):
    """Each cell reports the per-layer metrics it reported when six of
    them were still handed to every cell that reports what they move."""
    assert {m["name"] for m in MF.Cell(man, T.ROOT, cell).per_layer} == (
        metrics)


def test_slab_capacities_hold_the_lattice(man):
    """An MD slab cell's map() buckets hold a whole lattice plane where
    one lies on a slab face (about half of it crosses in a step), and its
    ghost_get holds every plane within r_cut of a face on either side."""
    import numpy as np
    for w in man["workloads"]:
        cell = MF.Cell(man, T.ROOT, w["name"])
        mesh = cell.workload.get("mesh")
        if not mesh or cell.config["app"] != "md":
            continue
        c, step = cell.config, cell.workload["step"]
        n, box, ndev = c["n_per_side"], c["box"], int(np.prod(mesh))
        planes = (np.arange(n) + 0.5) * box / n
        faces = np.arange(ndev + 1) * box / ndev
        plane = n * n
        on_face = np.isclose(planes[:, None], faces[None, :]).any()
        if on_face:
            assert step["bucket_cap"] >= plane, w["name"]
        r_cut = 3.0 * c["sigma"]
        near = max(max(int(np.sum((planes >= f - r_cut) & (planes < f))),
                       int(np.sum((planes >= f) & (planes < f + r_cut))))
                   for f in faces[1:-1])
        assert step["ghost_cap"] >= near * plane, w["name"]
