"""Device time per step outside Pallas kernels and collectives, ms (mean
over the chips used): the step engine's XLA work (cell list, candidate
gather, slot scatter, integrator, map())."""
import devtrace as DT


def read(ctx):
    per_dev = []
    for ops in ctx.trace.devices:
        special = DT.union_ns(DT.select(
            ops, lambda o: DT.is_pallas(o) or DT.is_collective(o)))
        per_dev.append(DT.union_ns(ops) - special)
    if not any(per_dev):
        return None
    return sum(per_dev) / len(per_dev) / 1e6 / ctx.steps
