"""Device busy microseconds per slot on the fleet backend (the chunk
program of sharding/sim): busy seconds of the traced window over the
slots its calls advanced."""


def read(ctx):
    if ctx.facts.get("backend") != "fleet" or ctx.slots <= 0:
        return None
    return 1e6 * ctx.busy_s / ctx.slots
