"""Mean device microseconds of one call of the Pallas route kernel
(kernels/slot_step.py): the sum of its events' durations in the trace over
their number.  Nothing is read where the trace holds no such event."""


def read(ctx):
    events = ctx.kernel_events
    if not events:
        return None
    return 1e6 * sum(events) / len(events)
