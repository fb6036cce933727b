"""CPU rehearsal of the chip benchmark: the md and vic drivers run end to
end at a tiny size in interpret mode, from data files in a fresh tree,
and print the benchmark's result line; the lower-precision control
comes out not correct; the real command refuses without a TPU."""
import json
import subprocess
import sys

import pytest

import chipbench_tiny as T

run = T.run

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("app", ["md", "vic"])
def test_rehearsal_result_line(tmp_path, app):
    root = T.write_tiny(tmp_path)
    cell = T.CELL_OF_APP[app]
    res = T.run_tiny(root, cell)
    assert list(res) == KEYS
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    rate = {"md": "particle_steps_per_s", "vic": "cell_updates_per_s"}[app]
    assert set(res["metrics"]) == {rate, "setup_s"}
    assert res["metrics"][rate]["value"] > 0
    assert res["device"]["count"] == 1
    for v in res["checks"].values():
        assert set(v) == {"value", "limit"}
    json.loads(json.dumps(res))        # one JSON line


@pytest.mark.parametrize("app", ["md", "vic"])
def test_lower_precision_control_is_not_correct(tmp_path, app):
    """The control fails the comparison: for MD the program's own bfloat16
    path (``precision="bf16x"``) in place of the float32 one; for VIC,
    whose bf16x path does not compile on a v5e, the plain reference with
    its M'4 products in bfloat16 in the program's place."""
    if app == "md":
        root = T.write_tiny(tmp_path, over={"precision": "bf16x"})
        res = T.run_tiny(root, T.CELL_OF_APP[app])
    else:
        root = T.write_tiny(tmp_path)
        res = T.run_tiny(root, T.CELL_OF_APP[app],
                         wrap=run.load_module("drivers", app).control)
    assert res["correct"] is False, res["checks"]


def test_command_refuses_without_tpu():
    p = subprocess.run(
        [sys.executable, str(T.BENCH / "run.py"), "--workload",
         "md216k_1chip", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=T.ROOT,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        run.peaks_for("TPU v99")
    assert run.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
