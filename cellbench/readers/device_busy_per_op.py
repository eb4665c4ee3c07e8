"""Seconds per operation in which something ran on the device: the union of
the device's operation intervals over the traced window, averaged over chips."""

from .. import trace


def read(ctx, spec):
    if not ctx.on_chip or not ctx.ops:
        return None
    busy = trace.busy_seconds(ctx.events, ctx.lo, ctx.hi)
    return busy / ctx.ops if busy > 0 else None
