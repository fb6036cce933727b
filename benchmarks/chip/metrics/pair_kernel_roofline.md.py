"""Share of its roofline the pair kernel reaches, %: the least time the
chips need for the LJ work the physics requires (``work/pair_kernel.py``)
over the kernel's device time per step."""
import devtrace as DT


def read(ctx):
    w = DT.load_module("work", "pair_kernel").count(ctx.config)
    return DT.roofline_pct(ctx, w["flops"], w["bytes"],
                           DT.per_step_ms(ctx, DT.is_pallas))
