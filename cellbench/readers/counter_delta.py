"""A process-wide counter of the program after the window minus before it
(all label sets summed)."""

from .report_counter_per_op import total


def read(ctx, spec):
    if ctx.counters_before is None or ctx.counters_after is None:
        return None
    return (total(ctx.counters_after, spec["counter"], {})
            - total(ctx.counters_before, spec["counter"], {}))
