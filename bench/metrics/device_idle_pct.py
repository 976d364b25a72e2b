"""Share of the traced window in which no operation ran on the device:
100 * (1 - busy / window), busy being the union of the device's event
intervals, averaged over the chips used."""


def read(ctx):
    if ctx.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
