"""The route kernel's share of its roofline: the least time the chip could
take for one call (bench/kernels/fleet_route.py: operations over the bf16
peak or bytes over HBM bandwidth, whichever is larger) over the mean time
of one call in the trace, in percent."""

import importlib.util
from pathlib import Path


def read(ctx):
    events = ctx.kernel_events
    if not events or ctx.peak is None:
        return None
    path = Path(__file__).resolve().parents[1] / "kernels" / "fleet_route.py"
    spec = importlib.util.spec_from_file_location("bench_kernel_fleet_route", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    least, _bound = mod.least_time(ctx.facts["kernel_shape"], ctx.peak)
    return 100.0 * least / (sum(events) / len(events))
