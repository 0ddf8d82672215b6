"""Facade and backend (``api/session.py``, ``api/backends.py``): time in
the ``search.prep`` spans (the facade's query checks, the host rotation of
the queries and their copy to the device) per whole ``bench.step`` span of
the traced stretch, mean, in milliseconds (``bench.stages.span_ms``).
Program span.  None where the trace has no such span."""
from bench import stages


def read(ctx):
    return stages.span_ms(ctx.trace, "search.prep")
