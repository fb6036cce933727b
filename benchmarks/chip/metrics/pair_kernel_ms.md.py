"""Device time of the cell-pair Pallas kernel per step, ms (mean over the
chips used). The MD step holds one Pallas kernel, the pair kernel; the
trace names it after the jitted function that encloses it, so it is
matched as the ``tpu_custom_call``."""
import devtrace as DT


def read(ctx):
    return DT.per_step_ms(ctx, DT.is_pallas)
