"""The megastep's share of the chips' bf16 peak, in %: the operations its
tokens required (``chipbench.flops``: every lane's tokens of the window at
their real context lengths, heads unpadded) over its device time times the
peak of every chip.  Layer: megastep."""
from chipbench import reduce

PROGRAM = "megastep"


def read(ctx):
    secs, execs = reduce.program_time(ctx.trace, ctx.win, PROGRAM)
    if not execs or not secs:
        return None
    peak = float(ctx.peaks["bf16_flops_per_s"])
    return 100.0 * ctx.window_flops / (secs * peak * ctx.chips)
