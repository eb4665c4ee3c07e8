"""Mean seconds of the host spans named `span` ({estimator} is filled in from
the configuration), from the trace."""

from .. import trace


def read(ctx, spec):
    name = spec["span"].format(estimator=ctx.est.ESTIMATOR)
    spans = trace.host_spans(ctx.events, name, ctx.lo, ctx.hi)
    if not spans:
        return None
    return sum(e - s for s, e in spans) / 1e9 / len(spans)
