"""Mesh nodes times steps completed in the window, over the window's
seconds on the host clock."""


def read(ctx):
    return ctx.work_per_step * ctx.steps / ctx.window_s
