"""The four-chip MD cell at a tiny size on four forced host devices: a
sound run is correct, and a run whose ghost exchange is left out (every
ppermute delivers zeros) is not."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

import chipbench_tiny as T

SCRIPT = textwrap.dedent("""
    import json, pathlib, sys
    sys.path.insert(0, {tests!r})
    import chipbench_tiny as T
    if {broken}:
        import jax.numpy as jnp
        from repro.core import runtime as RT
        RT.ppermute = lambda x, axis_name, perm: jnp.zeros_like(x)
    res = T.run_tiny(pathlib.Path({root!r}), "md857k_slab4", seconds=0.5)
    print(json.dumps(res))
""")


@pytest.mark.parametrize("broken", [False, True])
def test_slab4_exchange(tmp_path, broken):
    root = T.write_tiny(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(tests=str(T.HERE),
                                             root=str(root), broken=broken)],
        capture_output=True, text=True, env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    assert res["correct"] is (not broken), res["checks"]
