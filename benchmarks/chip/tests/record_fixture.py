#!/usr/bin/env python3
"""Record a small device trace of one cell, for the reader tests.

    python3 benchmarks/chip/tests/record_fixture.py --workload md216k_1chip \\
        --steps 2 --out benchmarks/chip/tests/fixtures/md216k_scoped.json.gz

Sets the cell up as ``run.py`` does (state from the seed, warm-up), traces
``--steps`` steps, each in a ``step`` host span, reduces the trace with
``devtrace.from_xplane``, keeps the devices the cell uses and saves it with
``DeviceTrace.to_json``. Prints the cell's per-layer metrics as read from
the saved trace. Needs the cell's chips.
"""
from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
import tempfile

import chipbench_tiny as T

R = T.run
DT = R.DT


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--seed", type=int, default=2 ** 40 + 7)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    man = R.MF.load(T.ROOT)
    cell = R.MF.Cell(man, T.ROOT, args.workload)
    R.enable_cache(T.ROOT)
    import jax
    used = jax.devices()[:cell.chips]
    driver = DT.load_module("drivers", cell.config["app"])
    sess = driver.setup(cell.config, cell.traffic, cell.workload,
                        R.seed32(args.seed), used)
    logdir = tempfile.mkdtemp(prefix="chipbench-fixture-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        for _ in range(args.steps):
            with jax.profiler.TraceAnnotation("step"):
                sess.step()
        sess.sync()
    finally:
        jax.profiler.stop_trace()
    try:
        tr = DT.from_xplane(logdir, sess.hlo_texts(), ("step",))
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    tr.devices = tr.devices[:cell.chips]
    with gzip.open(args.out, "wt") as f:
        json.dump(tr.to_json(), f)
    spans = tr.host_spans
    ctx = R.Ctx(steps=args.steps,
                window_s=(max(s[2] for s in spans) - spans[0][1]) / 1e9,
                setup_s=0.0, work_per_step=sess.work_per_step,
                chips=cell.chips, config=cell.config,
                peaks=R.peaks_for(used[0].device_kind), trace=tr)
    for m in cell.per_layer:
        print(m["name"], R.read_metric(m["name"], ctx), flush=True)
    print(f"wrote {args.out}: {sum(len(d) for d in tr.devices)} ops on "
          f"{len(tr.devices)} devices", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
