#!/usr/bin/env python3
"""Chip benchmark of the particle engine: one cell per process.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. Everything that
belongs to it is data found by name: ``configs/<config>.json`` (sizes and
the app), ``traffic/<traffic>.json`` (how the state is started and
stepped), ``workloads/<cell>.json`` (chips, mesh, step options,
capacities, the limits of the correctness check), and
``tests/tiny/<config>.json`` (the sizes the CPU tests cut a configuration
to). The app's driver is ``drivers/<app>.py``; each metric is read by
``metrics/<metric>.py`` and reported in the cells its ``workloads`` list
names; a kernel's work counts are in ``work/<kernel>.py``; the device
peaks in ``peaks.json``. A cell therefore joins with new files and list
entries in ``BENCHMARK.json``, and no edit to a file here.

A run makes its state from ``--seed`` on the device, warms up every
program the window calls, measures for ``--seconds``, then checks a
sample of the window's steps, drawn from the seed, against the plain
reference under ``reference/``. With ``--trace 1`` the window runs under
the JAX profiler and the per-layer metrics are read from the device
trace. The last line of standard output is one JSON object; the numbers
compared, each with its limit, are the last lines of standard error and
the last key of that object. Without a TPU, or with fewer chips than the
cell needs, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Callable, Optional  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import devtrace as DT  # noqa: E402
import manifest as MF  # noqa: E402
from devtrace import load_module  # noqa: E402

HOST_SPANS = ("step", "sample")


class Refused(RuntimeError):
    """The run cannot measure this cell here (no TPU, too few chips)."""


def seed32(seed: int) -> int:
    """A 31-bit seed from any whole number (seeds may exceed 32 bits)."""
    import numpy as np
    return int(np.random.SeedSequence(seed).generate_state(1)[0] >> 1)


@dataclasses.dataclass
class Ctx:
    """What a metric reader sees."""
    steps: int
    window_s: float
    setup_s: float
    work_per_step: float
    chips: int
    config: dict
    peaks: Optional[dict] = None
    trace: object = None


def peaks_for(kind: str) -> dict:
    with open(HERE / "peaks.json") as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} has no peaks in peaks.json "
                       f"(have {sorted(table)})")
    return table[kind]


def enable_cache(root: pathlib.Path) -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def window(sess, seconds: float, rng):
    """Step ``sess`` until ``seconds`` have passed. Keeps (input, output)
    of the last step and of one step drawn uniformly from the window by
    reservoir sampling from ``rng``. Returns (steps, failed, seconds,
    samples)."""
    import jax
    t0 = time.perf_counter()
    n = failed = 0
    prev = sess.snapshot()
    kept = last = None
    while True:
        with jax.profiler.TraceAnnotation("step"):
            failed += sess.step()
        n += 1
        with jax.profiler.TraceAnnotation("sample"):
            cur = sess.snapshot()
            last = (prev, cur)
            if rng.random() * n < 1.0:
                kept = last
            prev = cur
        if time.perf_counter() - t0 >= seconds:
            break
    sess.sync()
    dt = time.perf_counter() - t0
    samples = [kept] if kept is last else [kept, last]
    return n, failed, dt, samples


def read_metric(name: str, ctx: Ctx):
    val = load_module("metrics", name).read(ctx)
    if val is None:
        return None
    return val if isinstance(val, dict) else {"value": float(val)}


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             root: pathlib.Path = ROOT, require_tpu: bool = True,
             wrap: Optional[Callable] = None, t_start: Optional[float] = None,
             config_over: Optional[dict] = None, log=print) -> dict:
    """Run one cell; returns the result object. ``wrap(session)`` may
    replace the session (tests plant faults this way); ``config_over``
    replaces configuration keys (the control's lower precision)."""
    import numpy as np
    t_start = T_START if t_start is None else t_start
    man = MF.load(root)
    cell = MF.Cell(man, root, cell_name)
    cell.config.update(config_over or {})
    enable_cache(root)
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX finds platform {devs[0].platform!r}")
    if len(devs) < cell.chips:
        raise Refused(f"cell {cell_name} needs {cell.chips} chips, JAX "
                      f"finds {len(devs)}")
    used = devs[:cell.chips]
    peaks = peaks_for(used[0].device_kind) if trace else None
    driver = load_module("drivers", cell.config["app"])
    sess = driver.setup(cell.config, cell.traffic, cell.workload,
                        seed32(seed), used)
    if wrap is not None:
        sess = wrap(sess)
    setup_s = time.perf_counter() - t_start
    rng = np.random.default_rng(seed)
    logdir = None
    if trace:
        logdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        steps, failed, window_s, samples = window(sess, seconds, rng)
    finally:
        if trace:
            jax.profiler.stop_trace()
    peak_mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in used)
    ctx = Ctx(steps=steps, window_s=window_s, setup_s=setup_s,
              work_per_step=sess.work_per_step, chips=cell.chips,
              config=cell.config, peaks=peaks)
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": peak_mem}
    result = {"correct": None, "attempted": steps, "failed": failed,
              "metrics": {}, "device": device}
    if trace:
        try:
            tr = DT.from_xplane(logdir, sess.hlo_texts(), HOST_SPANS)
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
        tr.devices = tr.devices[:cell.chips]
        ctx.trace = tr
        busy = [DT.union_ns(ops) for ops in tr.devices]
        device["busy_s"] = sum(busy) / max(len(busy), 1) / 1e9
        device["window_s"] = window_s
        for m in cell.per_layer:
            val = read_metric(m["name"], ctx)
            if val is None:
                raise RuntimeError(f"metric {m['name']}: nothing to read in "
                                   f"cell {cell_name}, which it lists")
            result["metrics"][m["name"]] = {**val, "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": DT.top_ops(tr),
            "idle_gaps": DT.idle_gaps(tr)}
    else:
        for m in cell.end_to_end:
            val = read_metric(m["name"], ctx)
            result["metrics"][m["name"]] = {**val, "unit": m["unit"]}
    sess.release()
    checks = sess.check(samples)
    checks.append(("failed_steps", float(failed), 0.0))
    result["correct"] = all(v <= lim for _, v, lim in checks)
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in checks}
    for n, v, lim in checks:
        log(f"check {n} = {v!r} (limit {lim!r}) "
            f"{'ok' if v <= lim else 'FAILED'}", file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except Refused as e:
        print(f"run.py: refusing to run: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
