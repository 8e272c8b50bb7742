"""Device time per execution of the serving megastep in the operations of
its ``attend`` named scope (the attention core: ``paged.attend_local`` or a
decode kernel, and the merge of their partials), in ms, averaged over the
chips.  The operations are those inside ``jit_megastep`` executions, loops
and calls left out; each one's scope comes from the program's map of its
compiled megastep (``chipbench.attribution``).  Layer: attention."""
from chipbench import attribution


def read(ctx):
    return attribution.scope_ms_per_execution(ctx, "attend")
