"""Seconds one operation's table needs to cross from host to device at the
rate the harness timed in set-up with one `device_put` of the same table. A
time, not a share: the upload and the kernels may overlap."""


def read(ctx, spec):
    if not ctx.on_chip or not ctx.h2d_bytes_per_s:
        return None
    return ctx.cfg["rows"] * ctx.cfg["cols"] * 4.0 / ctx.h2d_bytes_per_s
