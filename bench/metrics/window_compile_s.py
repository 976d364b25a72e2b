"""Seconds of JAX lowering, compiling and persistent-cache reads inside the
traced window (JAX's own monitoring events; the study entry's host work)."""


def read(ctx):
    return ctx.window_compile_s
