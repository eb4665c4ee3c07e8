"""A counter of the operation's own run report (`fit_report_`), summed over
the label sets that include `labels`, averaged over the window's operations.
An operation that ran and did not touch the counter reads 0."""


def split_key(key):
    """`name{a=x,b=y}` -> (name, {a: x, b: y})."""
    if "{" not in key:
        return key, {}
    name, _, rest = key.partition("{")
    labels = dict(part.split("=", 1) for part in rest.rstrip("}").split(",") if "=" in part)
    return name, labels


def total(counters, name, labels):
    out = 0.0
    for key, value in counters.items():
        base, have = split_key(key)
        if base == name and all(have.get(k) == v for k, v in labels.items()):
            out += float(value)
    return out


def read(ctx, spec):
    if not ctx.report_counters:
        return None
    labels = spec.get("labels", {})
    vals = [total(c, spec["counter"], labels) for c in ctx.report_counters]
    return sum(vals) / len(vals)
