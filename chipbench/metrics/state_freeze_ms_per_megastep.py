"""Device time per execution of the serving megastep in the operations of
its ``state_freeze`` named scope (``engine._freeze_lanes``: the per-token
select that keeps refused and idle lanes' recurrent state), in ms, averaged
over the chips; read as ``attend_ms_per_megastep`` is.  Layer: megastep
(recurrent state)."""
from chipbench import attribution


def read(ctx):
    return attribution.scope_ms_per_execution(ctx, "state_freeze")
