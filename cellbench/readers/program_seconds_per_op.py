"""Device seconds an operation of the compiled programs whose name contains
`program`, in the traced window: what a program costs the device where no
floor from shapes can be named for it (a roofline wants one)."""

from .. import trace


def read(ctx, spec):
    if not ctx.on_chip or not ctx.ops:
        return None
    seconds = trace.program_seconds(ctx.events, spec["program"], ctx.lo, ctx.hi)
    return seconds / ctx.ops if seconds > 0 else None
