"""Seconds from the process's start to the first step of the window:
imports, reaching the chip, state from the seed, loading or compiling the
programs, warm-up steps."""


def read(ctx):
    return ctx.setup_s
