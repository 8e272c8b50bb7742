"""Host time on a serving round's critical path, in ms: the program's span
``repro.serve.round`` less its ``repro.serve.wait`` (the sync on the
megastep's outputs), summed over the window's rounds and divided by their
number.  The device waits for the host through all of it.  Layer: serving
loop and scheduler (host)."""
from chipbench import reduce


def read(ctx):
    total = reduce.program_ms_per_round(ctx.trace, ctx.win,
                                        reduce.PROGRAM_ROUND_SPAN)
    if total is None:
        return None
    wait = reduce.program_ms_per_round(ctx.trace, ctx.win,
                                       reduce.PROGRAM_SPAN + "serve.wait")
    return total - wait
