"""A number each operation's outputs carry (as the estimator's file names it),
averaged over the window's operations."""


def read(ctx, spec):
    vals = [o[spec["attribute"]] for o in ctx.op_outputs if spec["attribute"] in o]
    if not vals:
        return None
    return float(sum(vals)) / len(vals)
