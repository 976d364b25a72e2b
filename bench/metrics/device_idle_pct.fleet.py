"""Share of the traced window in which no operation ran on the device, in
the cells whose rate is `fleet_slots_per_s` (device_idle_pct.py reads the
same for the dense cells: each moves its own backend's rate)."""


def read(ctx):
    if ctx.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
