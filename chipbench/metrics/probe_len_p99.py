"""99th percentile of the page table's probe length over its live keys,
``PageTable.probe_p99(state["table"])``, read once after the window.
Layer: allocator.  None for a model with no paged cache."""


def read(ctx):
    return ctx.counters.get("probe_p99")
