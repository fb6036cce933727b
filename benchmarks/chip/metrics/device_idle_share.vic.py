"""Share of the traced window in which the device ran no operation, %:
the host's gaps between steps (dispatch, the per-step read of the
program's overflow counts)."""
import devtrace as DT


def read(ctx):
    return DT.idle_share_pct(ctx)
