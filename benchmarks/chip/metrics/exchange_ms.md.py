"""Device time of the exchange's own XLA work per step, ms (mean over the
chips used): the ops under the ``map`` and ``ghost_get`` scopes (bucket
packing, send selection, ghost shifts, merging), less the collectives,
which ``collective_ms.md`` reads. A one-chip step that names its layers
runs no exchange and reads 0; on several chips a trace without the two
scopes reads nothing."""
import devtrace as DT
import scopes as S


def read(ctx):
    ms = S.scope_ms(ctx, ("map", "ghost_get"), exclude=DT.is_collective)
    if ms is None and len(ctx.trace.devices) == 1 and S.named(ctx.trace):
        return 0.0
    return ms
