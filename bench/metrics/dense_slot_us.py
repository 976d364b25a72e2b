"""Device busy microseconds per configuration-slot on the dense backend
(the vmapped `lax.scan` of core/simulator): busy seconds of the traced
window over the configuration-slots its calls advanced."""


def read(ctx):
    if ctx.facts.get("backend") != "dense" or ctx.slots <= 0:
        return None
    return 1e6 * ctx.busy_s / ctx.slots
