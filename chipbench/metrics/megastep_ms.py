"""Device time per execution of the serving megastep program (jitted name
``megastep``), in ms, averaged over the chips.  Layer: megastep."""
from chipbench import reduce

PROGRAM = "megastep"


def read(ctx):
    secs, execs = reduce.program_time(ctx.trace, ctx.win, PROGRAM)
    if not execs:
        return None
    return 1e3 * secs / execs
