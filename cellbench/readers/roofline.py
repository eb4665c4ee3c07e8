"""A kernel's share of its roofline: the least seconds the chips could take
for the work the algorithm needs (from shapes: the larger of operations over
peak FLOP/s and bytes over peak bytes/s) over the device seconds of the
compiled programs (`program`) or operations (`op`) whose name contains the
given text, in the traced window."""

from .. import trace, work


def read(ctx, spec):
    if not ctx.on_chip or not ctx.ops:
        return None
    if "program" in spec:
        seconds = trace.program_seconds(ctx.events, spec["program"], ctx.lo, ctx.hi)
    else:
        seconds = trace.op_seconds(ctx.events, spec["op"], ctx.lo, ctx.hi)
    if seconds <= 0:
        return None
    floor = work.floor_seconds(ctx.est.kernel_work(ctx.cfg), ctx.peaks, ctx.chips)
    ctx.notes[spec.get("note", "roofline") + "_bound"] = floor["bound"]
    return 100.0 * floor["seconds"] * ctx.ops / seconds
