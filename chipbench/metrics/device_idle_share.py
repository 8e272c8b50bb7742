"""Share of the traced window, in %, in which no operation ran on the
device: 1 - (union of the device's op intervals) / window, averaged over the
chips.  Layer: device."""
from chipbench import reduce


def read(ctx):
    if not ctx.trace.ops:
        return None
    span = (ctx.win[1] - ctx.win[0]) / 1e9
    return 100.0 * (1.0 - reduce.device_busy_s(ctx.trace, ctx.win) / span)
