"""The whole operation's share of the chips' peak: operations the algorithm
needs (from shapes) times operations completed, over window seconds times
chips times peak FLOP/s."""


def read(ctx, spec):
    if not ctx.on_chip or not ctx.ops or ctx.window_s <= 0:
        return None
    flops = ctx.est.fit_work(ctx.cfg)["flops"] * ctx.ops
    return 100.0 * flops / (ctx.window_s * ctx.chips * ctx.peaks["flops_per_s"])
