"""Forced-host-device-count env plumbing for the multi-device harness.

jax-free on purpose: the forcing flag must land in XLA_FLAGS *before* jax
initializes its backend, so the three consumers (tests/distributed/conftest,
tests/_dist_launcher, benchmarks/bench_distributed's child) import this
module ahead of any jax import. One definition — the device count and the
append-if-absent logic cannot drift between them.
"""
from __future__ import annotations

import re

FORCED_DEVICE_COUNT = 8
FORCE_FLAG = f"--xla_force_host_platform_device_count={FORCED_DEVICE_COUNT}"

_FORCE_PAT = re.compile(r"--xla_force_host_platform_device_count=\d+")


def ensure_forced_host_devices(env) -> None:
    """Force exactly ``FORCED_DEVICE_COUNT`` host devices in
    ``env['XLA_FLAGS']`` (any mutable mapping, e.g. ``os.environ`` or a
    subprocess env dict). A pre-existing force with a different count is
    REPLACED, not kept — the multi-device suite is built for exactly 8
    devices (submeshes carve out fewer), and inheriting e.g. a stray
    2-device force from the caller's environment would make the whole child
    suite skip."""
    flags = env.get("XLA_FLAGS", "")
    if _FORCE_PAT.search(flags):
        env["XLA_FLAGS"] = _FORCE_PAT.sub(FORCE_FLAG, flags)
    else:
        env["XLA_FLAGS"] = (flags + " " + FORCE_FLAG).strip()


CAVEAT_TAG = "forced-host-devices-shared-cpu"


def tag_rows(rows: list) -> list:
    """Stamp the shared honesty marker onto relayed benchmark CSV rows:
    every row produced on forced host devices carries the same
    ``caveat=forced-host-devices-shared-cpu`` suffix, so downstream
    consumers can't mistake a shared-CPU memcpy 'network' for hardware."""
    return [f"{ln};caveat={CAVEAT_TAG}" for ln in rows]


def write_artifact(path, rows: list, caveat: str) -> None:
    """Mirror benchmark CSV rows into a repro-fleet-metrics/v1 JSON
    artifact stamped with both the bench-specific ``caveat`` prose and the
    shared ``CAVEAT_TAG``. One definition — the schema and the caveat
    stamping cannot drift between bench_overlap / bench_pencil /
    bench_reuse. ``path`` is a pathlib.Path; write failures are reported,
    never raised (benchmark output must never kill the run)."""
    import json
    import sys
    payload = {
        "schema": "repro-fleet-metrics/v1",
        "caveat": caveat,
        "caveat_tag": CAVEAT_TAG,
        "device_config": f"forced-host-devices (XLA {FORCE_FLAG})",
        "rows": [dict(zip(("name", "us_per_call", "derived"),
                          ln.split(",", 2))) for ln in rows],
    }
    try:
        path.parent.mkdir(exist_ok=True)
        path.write_text(json.dumps(payload, indent=2) + "\n")
    except OSError as e:
        print(f"{path.name}: could not write: {e}", file=sys.stderr)


def run_forced_host_child(file: str, row_prefix: str, *,
                          timeout: int = 1800) -> list:
    """The shared parent half of the ``--child`` re-exec pattern: device
    count is locked at first jax backend init, so multi-device benchmark
    rows are produced by re-running ``file`` as a subprocess with the
    forcing flag set, and relaying the stdout lines starting with
    ``row_prefix``. The child is pinned to the CPU (``JAX_PLATFORMS=cpu``):
    forced host devices are CPU devices, and on a chip host the parent
    already holds the accelerator. A failed child (nonzero exit or no
    rows) raises, with its stderr tail in the message."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    ensure_forced_host_devices(env)
    r = subprocess.run([sys.executable, os.path.abspath(file), "--child"],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    rows = [ln for ln in r.stdout.splitlines() if ln.startswith(row_prefix)]
    if r.returncode != 0 or not rows:
        raise RuntimeError(f"{os.path.basename(file)} child failed "
                           f"(rc={r.returncode}):\n{r.stderr[-2000:]}")
    return rows
