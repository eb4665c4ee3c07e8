"""A process-wide counter of the program after the window minus before it,
summed over the label sets that include `labels`, over the window's
operations: what one operation adds to a counter that no per-operation report
carries (a transform has no `fit_report_`). The program keeps every span's
seconds in counter form too (`span.seconds{span=<name>}`,
`span.calls{span=<name>}`), so this is also the mean seconds an operation
spends inside a host span, traced or not. A program that has no such counter
(an older commit) reads nothing, not 0."""

from .report_counter_per_op import split_key, total


def read(ctx, spec):
    if ctx.counters_before is None or ctx.counters_after is None or not ctx.ops:
        return None
    name, labels = spec["counter"], spec.get("labels", {})
    if not any(split_key(key)[0] == name for key in ctx.counters_after):
        return None
    return (total(ctx.counters_after, name, labels)
            - total(ctx.counters_before, name, labels)) / ctx.ops
