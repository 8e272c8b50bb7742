"""Host time per megastep round, in ms: the harness spans around the
batcher's ``_forcing``, ``_absorb``, ``_apply_plan``, the scheduler's
``plan_round`` and the client's submission, summed over the window's rounds
and divided by their number.  Layer: serving loop and scheduler (host)."""
from chipbench import reduce


def read(ctx):
    n = reduce.span_count(ctx.trace, ctx.win, reduce.ROUND_SPAN)
    if not n:
        return None
    return 1e3 * reduce.span_s(ctx.trace, ctx.win, ctx.host_spans) / n
