"""Host time per serving round in the program's span ``repro.serve.free``
(``ContinuousBatcher._free_lanes``: the finished lanes' pages handed back
to the page table, ``repro.pt.delete`` and ``repro.pt.count`` within), in
ms, over the window's rounds.  Layer: allocator."""
from chipbench import reduce


def read(ctx):
    return reduce.program_ms_per_round(ctx.trace, ctx.win,
                                       reduce.PROGRAM_SPAN + "serve.free")
