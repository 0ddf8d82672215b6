"""Backend (``api/backends.py``): device-idle time inside the
``search.fetch`` spans (the copy of the engine's outputs to the host and
their conversion after the last device operation) per whole ``bench.step``
span of the traced stretch, mean, in milliseconds
(``bench.stages.idle_in``).  Program span on the device trace's clock.
None where the trace has no such span."""
from bench import stages


def read(ctx):
    return stages.idle_in(ctx.trace, "search.fetch")
