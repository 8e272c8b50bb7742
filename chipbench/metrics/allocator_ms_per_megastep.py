"""Device time per execution of the serving megastep in the operations of
its ``allocator`` named scope (``engine._page_ops``: the page table's
incremental allocation, the block-table read and the per-chip page
compaction), in ms, averaged over the chips; read as
``attend_ms_per_megastep`` is.  Layer: allocator."""
from chipbench import attribution


def read(ctx):
    return attribution.scope_ms_per_execution(ctx, "allocator")
