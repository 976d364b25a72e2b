"""Seconds of JAX lowering, compiling and persistent-cache reads inside the
traced window, in the cells whose rate is `fleet_slots_per_s`
(window_compile_s.py reads the same for the dense cells: each moves its
own backend's rate)."""


def read(ctx):
    return ctx.window_compile_s
