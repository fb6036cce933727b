"""Share of its roofline the M'4 kernels reach, %: the least time the chip
needs for the interpolation work a step requires (``work/m4_kernel.py``)
over the kernels' device time per step."""
import devtrace as DT


def read(ctx):
    w = DT.load_module("work", "m4_kernel").count(ctx.config)
    ms = DT.load_module("metrics", "m4_kernel_ms.vic").read(ctx)
    return DT.roofline_pct(ctx, w["flops"], w["bytes"], ms)
